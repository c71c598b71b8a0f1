"""Pallas TPU kernels for hot ops.

`flash_attention`: blockwise attention computed entirely in VMEM with
online softmax — O(seq) memory instead of the O(seq^2) score matrix.
Grid is (q_blocks, k_blocks); the k axis iterates sequentially (TPU grids
run minor-axis-last), carrying the running max / denominator / weighted
accumulator in VMEM scratch that persists across k iterations. Q·Kᵀ and
P·V ride the MXU via `jnp.dot(..., preferred_element_type=f32)`; masking
(causal + padded tail) happens on the VPU.

This kernel is the single-device building block the ring attention in
`parallel/ring.py` composes across chips (K/V rotation over ICI); it is
also used directly by `models.TransformerLM` for unsharded TPU runs.
It compiles for the TPU unless the caller passes ``interpret=True``
(the CPU tests do) — it never picks interpret mode from the backend by
itself, so a chip run cannot be an interpreted one without saying so.
Production CPU paths use `parallel.ring.full_attention`.

Differentiation: the forward pass is the kernel; the backward pass is
`full_attention`'s VJP, recomputed from q/k/v (`jax.custom_vjp` — a
`pallas_call` has no transpose rule of its own). A fused backward
kernel is ROADMAP Queue 2 item 3.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
    *, scale: float, causal: bool, seq_len: int, blk_q: int, blk_k: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # Causal fast-skip: whole k-block strictly above the diagonal.
    needed = jnp.logical_or(
        not causal, j * blk_k <= i * blk_q + (blk_q - 1)
    )

    @pl.when(needed)
    def _step():
        q = q_ref[:].astype(jnp.float32)
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        q_pos = i * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = k_pos < seq_len  # padded tail keys contribute nothing
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        # NB: f32-typed constants — x64-mode weak f64 literals trip Mosaic
        s = jnp.where(mask, s, jnp.float32(_NEG_INF))

        m_prev = m_sc[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, jnp.float32(0.0))
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, 0] = alpha * l_sc[:, 0] + jnp.sum(p, axis=-1)
        acc_sc[:] = alpha[:, None] * acc_sc[:] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_sc[:, 0] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = l_sc[:, 0]
        l = jnp.where(l == jnp.float32(0.0), jnp.float32(1.0), l)
        o_ref[:] = (acc_sc[:] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Single-device blockwise attention. q/k/v: (seq, head_dim)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    return _flash(
        q, k, v, bool(causal), float(scale), block_q, block_k,
        bool(interpret),
    )


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret):
    seq, d = q.shape

    blk_q = min(block_q, max(8, seq))
    blk_k = min(block_k, max(8, seq))
    pad_q = (-seq) % blk_q
    pad_k = (-seq) % blk_k
    qp = jnp.pad(q, ((0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, pad_k), (0, 0))) if pad_k else v
    nq = qp.shape[0] // blk_q
    nk = kp.shape[0] // blk_k

    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        seq_len=seq,
        blk_q=blk_q,
        blk_k=blk_k,
    )
    out = pl.pallas_call(
        kernel,
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((blk_q, d), lambda i, j: (i, jnp.int32(0))),
            pl.BlockSpec((blk_k, d), lambda i, j: (j, jnp.int32(0))),
            pl.BlockSpec((blk_k, d), lambda i, j: (j, jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((blk_q, d), lambda i, j: (i, jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),  # running max
            pltpu.VMEM((blk_q, 1), jnp.float32),  # running denominator
            pltpu.VMEM((blk_q, d), jnp.float32),  # weighted accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:seq] if pad_q else out


_flash = jax.custom_vjp(_flash_forward, nondiff_argnums=(3, 4, 5, 6, 7))


def _flash_fwd(q, k, v, *static):
    return _flash_forward(q, k, v, *static), (q, k, v)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    from ..parallel.ring import full_attention

    _, vjp = jax.vjp(
        lambda q, k, v: full_attention(q, k, v, causal=causal, scale=scale),
        *res,
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)
