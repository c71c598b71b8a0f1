"""Sequence/context parallelism: ring attention + all-to-all helpers.

The reference predates transformers (SURVEY.md §5: "long-context /
sequence parallelism: absent"), but this framework treats long-sequence
scale as first-class: sequences shard over the mesh's ``data`` axis and
attention runs BLOCKWISE around the ring —

- each device holds its local Q block and a rotating K/V block;
- at every step it accumulates flash-style online-softmax partials
  (running max + denominator, so numerics match full attention), then
  passes its K/V block to the next device with `lax.ppermute` over ICI;
- after ``ndev`` steps every Q block has attended to the full sequence
  with peak memory O(seq/ndev) per chip and compute/communication
  overlapped by XLA.

This is the standard Ring Attention construction (Liu et al. 2023) built
from XLA collectives. `seq_all_to_all` provides the Ulysses-style
alternative: re-shard between sequence-sharded and head-sharded layouts
with a single `lax.all_to_all`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "full_attention", "seq_all_to_all"]


def _online_step(q, k_blk, v_blk, m, l, o, scale, mask):
    """One blockwise online-softmax accumulation step (flash-style).

    q: (Sq, d); k_blk/v_blk: (Sk, d); m,l: (Sq,); o: (Sq, d).
    mask: (Sq, Sk) boolean, True = attend.
    """
    scores = (q @ k_blk.T) * jnp.float32(scale)  # (Sq, Sk)
    scores = jnp.where(mask, scores, -jnp.inf)
    m_blk = jnp.max(scores, axis=-1)  # (Sq,)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked blocks: exp(-inf - -inf) -> exp(0); weight is 0 anyway
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(scores - safe_m[:, None])  # (Sq, Sk)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
    l_new = alpha * l + jnp.sum(p, axis=-1)
    o_new = alpha[:, None] * o + p @ v_blk
    return m_new, l_new, o_new


def _ring_shard(q, k, v, *, axis_name: str, causal: bool, scale: float):
    """Per-shard ring attention body (runs under shard_map)."""
    ndev = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    sq, d = q.shape[-2], q.shape[-1]
    sk = k.shape[-2]
    q32 = q.astype(jnp.float32)

    def body(i, carry):
        k_blk, v_blk, m, l, o = carry
        # which shard's K/V we currently hold
        src = (my_idx - i) % ndev
        if causal:
            q_pos = my_idx * sq + jnp.arange(sq)
            k_pos = src * sk + jnp.arange(sk)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((sq, sk), dtype=bool)
        m, l, o = _online_step(
            q32, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32),
            m, l, o, scale, mask,
        )
        # rotate K/V around the ring (ICI neighbor exchange)
        perm = [(j, (j + 1) % ndev) for j in range(ndev)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, o

    m0 = jnp.full((sq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((sq,), jnp.float32)
    o0 = jnp.zeros((sq, d), jnp.float32)
    # under VMA tracking the loop carry must enter with the same
    # device-variance it leaves with (it picks up axis variance from the
    # rotating K/V, the axis_index masks, and q itself)
    vma = frozenset({axis_name}).union(
        *(getattr(x.aval, "vma", frozenset()) for x in (q, k, v))
    )
    m0, l0, o0 = (
        lax.pcast(x, tuple(sorted(vma)), to="varying") for x in (m0, l0, o0)
    )
    _, _, m, l, o = lax.fori_loop(0, ndev, body, (k, v, m0, l0, o0))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (shouldn't happen)
    return (o / l[:, None]).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "data",
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention over sequence-sharded q/k/v.

    Inputs are (seq, head_dim) arrays (vmap over batch/head axes outside),
    logically full-length; the function shards the sequence over ``axis``,
    runs the blockwise ring, and returns the full-length output with the
    same sharding. Sequence length must divide the axis size.
    """
    if scale is None:
        scale = float(1.0 / np.sqrt(q.shape[-1]))
    fn = functools.partial(
        _ring_shard, axis_name=axis, causal=causal, scale=scale
    )
    spec = P(axis, None)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def full_attention(q, k, v, *, causal=False, scale=None):
    """Reference single-device attention (for conformance tests)."""
    if scale is None:
        scale = float(1.0 / np.sqrt(q.shape[-1]))
    scores = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * scale
    if causal:
        n, m = scores.shape
        mask = jnp.arange(n)[:, None] >= jnp.arange(m)[None, :]
        scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return (w @ v.astype(jnp.float32)).astype(q.dtype)


def seq_all_to_all(
    x: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "data",
    seq_axis: int,
    head_axis: int,
) -> jax.Array:
    """Ulysses-style re-shard: move the mesh sharding from the sequence
    axis to the head axis (or back) with one `lax.all_to_all` over ICI.

    x is the logical full array; sharding flips from ``seq_axis`` to
    ``head_axis``. Both axis sizes must divide the mesh axis size.
    """
    ndev = mesh.shape[axis]
    if x.shape[seq_axis] % ndev or x.shape[head_axis] % ndev:
        raise ValueError(
            f"seq axis {x.shape[seq_axis]} and head axis {x.shape[head_axis]}"
            f" must divide mesh axis size {ndev}"
        )

    in_spec = [None] * x.ndim
    in_spec[seq_axis] = axis
    out_spec = [None] * x.ndim
    out_spec[head_axis] = axis

    def shard_fn(xs):
        return lax.all_to_all(
            xs, axis, split_axis=head_axis, concat_axis=seq_axis, tiled=True
        )

    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=P(*in_spec),
        out_specs=P(*out_spec),
        check_vma=False,
    )(x)
