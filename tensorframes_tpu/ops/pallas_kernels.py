"""Pallas TPU kernels for hot ops.

`flash_attention`: blockwise attention computed entirely in VMEM with
online softmax — O(seq) memory instead of the O(seq^2) score matrix.
Grid is (batch, heads, q_blocks, k_blocks); the k axis iterates
sequentially (TPU grids run minor-axis-last), carrying the running max /
denominator / weighted accumulator in VMEM scratch that persists across
k iterations. Q·Kᵀ and P·V ride the MXU in the operands' own dtype with
float32 accumulation; masking (causal + padded tail) happens on the VPU,
and only on the blocks that need it (`_edge`: a block crossing the
diagonal or a band's edge, or holding padded keys); every other block
runs a body with no iota, compare or select, which gives the same
numbers. A causal call without a window has no grid step above the
diagonal: its last grid axis walks the list of the (query block, key
block) pairs it computes (`_visits`), query block by query block, from
a scalar-prefetch table (on a v5e at 32,768 positions and 1,024-blocks
the latent kernel's layer took 92.2 ms, 110.4 with every block masked on
the full grid).
A query head reads the key/value head of its group (grouped-query
attention) through the block index, so nothing is repeated in memory.
``v`` may have a head width of its own (the output and the accumulator
take it), and the score may have a second part, ``q2 · k2ᵀ`` added to
``q · kᵀ`` before the scale and the mask, whose keys ``k2`` have heads of
their own count (latent attention: one rotary key a token for all heads,
found through the block index like a grouped key head). With a causal
``window`` the grid is BANDED: a query block's key axis has as many steps
as the longest band holds key blocks, step j visits the band's (first +
j)-th block, and a block wholly outside the band is never read (a
sliding-window layer costs its band, not its causal prefix).

This kernel is the single-device building block the ring attention in
`parallel/ring.py` composes across chips (K/V rotation over ICI); it is
also used directly by `models.TransformerLM` for unsharded TPU runs.
It compiles for the TPU unless the caller passes ``interpret=True``
(the CPU tests do) — it never picks interpret mode from the backend by
itself, so a chip run cannot be an interpreted one without saying so.
Production CPU paths use `parallel.ring.full_attention`.

`sparse_attention` (PR 40): the same kernel with two more operands, a
selection mask (batch, seq, seq) int8 that keeps each query's chosen keys
(one mask for all heads) and a sink logit a head that joins the softmax's
denominator at the end (under the running max). Blocks above the diagonal
are never visited; every other block is a dense pass masked by the
selection (and by the positions on the diagonal's blocks).

`index_scores` (PR 40): a lightning indexer's scores of ONE block of
queries against every key of the window, ``Σ_j w_j ReLU(q_j · k)`` over
the index heads (one key a position for all of them), causal, in float32;
the block's first position is a scalar-prefetch operand, so that blocks of
keys after the block's last query are skipped and the kernel is one
program for every block of a loop. The caller takes the block's top-k
directly after: the window's score matrix is never written.

`index_top_k`: that top-k without a sort. A tile of 32 queries' score
rows is in VMEM; the k-th largest score a row is found by 32 counts of a
bisection on the scores' order-preserving int32 image, then one prefix
count ranks the ties at it and places each kept key, and a compaction of
log2(k) lane shifts lists the keys in ascending order.

`ssd_scan`: a state-space scan (Mamba-2's recurrence ``S_t = exp(Δ_t A)
S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t + D x_t``) computed a chunk of
positions at a time: inside a chunk the recurrence is three matmuls (the
scores ``C Bᵀ`` under the decay between two positions, the carried state's
part, the state handed on), and the state, float32, stays in VMEM scratch
from one chunk of a sequence to the next. The grid runs rows × groups in
parallel and a sequence's chunks in order. No backward pass.

`head_logprob`: a language-model head's log-probability of each token's
target, ``x·w[:, t] - logsumexp(x·w)``, a tile of tokens at a time against
the vocabulary's tiles streaming past, with an online max and sum of
exponentials (flash attention's, over the vocabulary): the float32 logits
live in VMEM a tile at a time and never reach HBM. No backward pass.

Differentiation (`flash_attention`): the forward pass is the kernel; the backward pass is
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

__all__ = ["flash_attention", "band_pairs", "block_classes", "ssd_scan", "sparse_attention",
           "index_scores", "index_top_k", "head_logprob", "head_tiles"]

_NEG_INF = -1e30


def _band(i, blk_q: int, blk_k: int, window: int, nk: int):
    """(first, last) key block of query block ``i``'s band: the blocks that
    hold a key s with ``t - window < s <= t`` for some query t of the
    block (int32; `lax.div`, as in the index maps)."""
    first = jax.lax.div(jnp.maximum(i * blk_q - (window - 1), 0), jnp.int32(blk_k))
    last = jnp.minimum(jax.lax.div(i * blk_q + (blk_q - 1), jnp.int32(blk_k)), nk - 1)
    return first, last


def _edge(i, kb, *, blk_q: int, blk_k: int, seq_len: int, causal: bool,
          window: Optional[int]):
    """Whether key block ``kb`` holds a key that some query of query block
    ``i`` does not see: a padded key, a key after a query (the block
    crosses the diagonal) or a key ``window`` or more before one (it
    crosses the band's lower edge). Such a block runs the masked body;
    every other computed block is whole. Python ints on the host, int32
    scalars in the kernel."""
    edge = (kb + 1) * blk_k > seq_len
    if causal:
        edge = edge | ((kb + 1) * blk_k - 1 > i * blk_q)
    if window is not None:
        edge = edge | (kb * blk_k + window <= i * blk_q + (blk_q - 1))
    return edge


def _visits(seq: int, blk_q: int, blk_k: int, window: Optional[int]):
    """The (query block, key block) pairs a causal call computes, query
    block by query block, key blocks ascending: every block that holds a
    key some query of the block sees."""
    nq, nk = -(-seq // blk_q), -(-seq // blk_k)
    first = lambda i: 0 if window is None else max(i * blk_q - (window - 1), 0) // blk_k
    return [(i, kb) for i in range(nq)
            for kb in range(first(i), min((i * blk_q + blk_q - 1) // blk_k, nk - 1) + 1)]


def _blocks(seq: int, block_q: int, block_k: int):
    """The query and key blocks the kernel takes at ``seq`` positions."""
    return min(block_q, max(8, seq)), min(block_k, max(8, seq))


def band_pairs(seq: int, block_q: int, block_k: int, window: int) -> int:
    """The (query block, key block) pairs `flash_attention` computes for
    one head over ``seq`` positions under ``window`` at these blocks."""
    return len(_visits(seq, *_blocks(seq, block_q, block_k), window))


@functools.lru_cache(maxsize=64)
def block_classes(seq: int, block_q: int, block_k: int,
                  window: Optional[int] = None) -> tuple:
    """(inner, edge): the (query block, key block) pairs a causal
    `flash_attention` or `sparse_attention` computes for one head over
    ``seq`` positions at these blocks (under ``window``, if given) without
    the positional mask (every key seen by every query of the block) and
    with it (`_edge`)."""
    blk_q, blk_k = _blocks(seq, block_q, block_k)
    visits = _visits(seq, blk_q, blk_k, window)
    edge = sum(bool(_edge(i, kb, blk_q=blk_q, blk_k=blk_k, seq_len=seq, causal=True,
                          window=window)) for i, kb in visits)
    return len(visits) - edge, edge


def _flash_kernel(
    *refs,
    scale: float, causal: bool, seq_len: int, blk_q: int, blk_k: int,
    selected: bool = False, sink: bool = False, window: Optional[int] = None,
    nk: int = 0, listed: bool = False,
):
    # ``listed``: the grid's last axis walks a list of (query block, key
    # block) pairs, a scalar-prefetch table before the operands
    if listed:
        pairs_ref, *refs = refs
    q_ref, k_ref, v_ref, *rest = refs
    # before the output: a second score part's two blocks, then (sparse
    # attention) the block of the selection mask and the head's sink logit
    rest, (o_ref, m_sc, l_sc, acc_sc) = list(rest[:-4]), rest[-4:]
    sink_ref = rest.pop() if sink else None
    sel_ref = rest.pop() if selected else None
    second = rest

    needed = True
    if listed:  # step p is pair p: its first and last key blocks open and close the row
        p = pl.program_id(2)
        i, kb = pairs_ref[2 * p], pairs_ref[2 * p + 1]
        opens = kb == 0
        closes = kb == jnp.minimum(jax.lax.div(i * blk_q + (blk_q - 1), jnp.int32(blk_k)),
                                   nk - 1)
    else:
        i, j = pl.program_id(2), pl.program_id(3)
        opens, closes = j == 0, j == pl.num_programs(3) - 1
        kb = j
        if window is not None:  # step j of the band visits its (first + j)-th block
            first, last = _band(i, blk_q, blk_k, window, nk)
            kb = first + j
            needed = kb <= last

    @pl.when(opens)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    def step(masked: bool):
        # operands stay in their own dtype (bfloat16 rides the MXU at its
        # full rate); both products accumulate in float32
        q, k, v = q_ref[:], k_ref[:], v_ref[:]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if second:
            s = s + jax.lax.dot_general(
                second[0][:], second[1][:], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        s = s * scale

        mask = None
        if masked:  # an edge block: the positions say which keys each query sees
            q_pos = i * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            k_pos = kb * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            mask = k_pos < seq_len  # padded tail keys contribute nothing
            if causal:
                mask = jnp.logical_and(mask, q_pos >= k_pos)
            if window is not None:  # the band: the last `window` keys up to the query
                mask = jnp.logical_and(mask, k_pos > q_pos - window)
        if selected:  # only the keys the query's selection holds, on every block
            chosen = sel_ref[:] != 0
            mask = chosen if mask is None else jnp.logical_and(mask, chosen)
        # the mask enters through the max and after the exponential, so
        # both bodies compute ``exp(s - m_new)`` from one expression (a
        # compiler that fuses the scale into the subtraction does so in both)
        m_prev = m_sc[:, 0]
        # NB: f32-typed constants — x64-mode weak f64 literals trip Mosaic
        seen = s if mask is None else jnp.where(mask, s, jnp.float32(_NEG_INF))
        m_new = jnp.maximum(m_prev, jnp.max(seen, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if mask is not None:  # an unseen key, also in a row with nothing seen yet
            p = jnp.where(mask, p, jnp.float32(0.0))
        alpha = jnp.exp(m_prev - m_new)
        l_sc[:, 0] = alpha * l_sc[:, 0] + jnp.sum(p, axis=-1)
        acc_sc[:] = alpha[:, None] * acc_sc[:] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_sc[:, 0] = m_new

    # a whole block runs the body without the positional mask; on a whole
    # block the mask is all true, so both bodies give the same numbers
    edge = _edge(i, kb, blk_q=blk_q, blk_k=blk_k, seq_len=seq_len, causal=causal,
                 window=window)
    pl.when(jnp.logical_and(needed, edge))(lambda: step(True))
    pl.when(jnp.logical_and(needed, jnp.logical_not(edge)))(lambda: step(False))

    @pl.when(closes)
    def _finish():
        l, acc = l_sc[:, 0], acc_sc[:]
        if sink:  # exp(sink) joins the denominator, under the same max
            m, logit = m_sc[:], sink_ref[:]  # (blk_q, 1), (1, 1)
            top = jnp.maximum(m, logit)
            keep = jnp.exp(m - top)
            l = (l_sc[:] * keep + jnp.exp(logit - top))[:, 0]
            acc = acc * keep
        l = jnp.where(l == jnp.float32(0.0), jnp.float32(1.0), l)
        o_ref[:] = (acc / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q2: Optional[jax.Array] = None,
    k2: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Single-device blockwise attention. q/k/v: one head ``(seq,
    head_dim)``, or ``(batch, heads, seq, head_dim)`` with k and v
    holding ``kv_heads`` heads, each serving ``heads // kv_heads``
    consecutive query heads (grouped-query attention: the kernel reads a
    kv head's blocks where its query heads ask for them, nothing is
    repeated in memory). ``v``'s head width may differ from q's and k's:
    the output has ``v``'s. ``q2`` ``(batch, heads, seq, d2)`` and ``k2``
    ``(batch, kv2_heads, seq, d2)``, given together, are a second part of
    the score, ``(q kᵀ + q2 k2ᵀ) * scale``, ``kv2_heads`` dividing
    ``heads`` as ``kv_heads`` does (the default scale is
    ``1 / sqrt(head_dim + d2)``). ``window`` (a static int, causal only):
    query t attends to the keys s with ``t - window < s <= t``, the last
    ``window`` keys including its own, on a BANDED grid: a query block's
    key axis visits only the key blocks that meet its band
    (`_visits`), keys of a visited block outside the band are
    masked, and a block wholly outside it is never read."""
    if (q2 is None) != (k2 is None):
        raise ValueError("q2 and k2 are the two sides of one score part: give both")
    if q2 is not None and (q2.ndim != 4 or q.ndim != 4):
        raise ValueError("a second score part takes (batch, heads, seq, width) arrays")
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1] + (0 if q2 is None else q2.shape[-1]))
    if window is not None and (not causal or int(window) < 1):
        raise ValueError(f"window = {window!r}: a window is a causal band of one key or more")
    for name, keys in (("key/value", k), ("second-part key", k2)):
        if keys is not None and q.ndim == 4 and q.shape[1] % keys.shape[1]:
            raise ValueError(
                f"{q.shape[1]} query heads do not divide over {keys.shape[1]} "
                f"{name} heads"
            )
    return _flash(
        q, k, v, None if q2 is None else (q2, k2), bool(causal), float(scale),
        block_q, block_k, bool(interpret), None if window is None else int(window),
    )


def _flash_forward(q, k, v, second, causal, scale, block_q, block_k, interpret, window):
    return _flash_call(q, k, v, second, causal, scale, block_q, block_k, interpret,
                       window=window)


def _flash_call(q, k, v, second, causal, scale, block_q, block_k, interpret,
                selection=None, sink=None, window=None):
    if q.ndim == 2:
        out = _flash_call(
            q[None, None], k[None, None], v[None, None], second,
            causal, scale, block_q, block_k, interpret, window=window,
        )
        return out[0, 0]
    batch, heads, seq, d = q.shape
    dv = v.shape[-1]

    blk_q, blk_k = _blocks(seq, block_q, block_k)
    pad_q = (-seq) % blk_q
    pad_k = (-seq) % blk_k
    pad = lambda a, n: jnp.pad(a, ((0, 0), (0, 0), (0, n), (0, 0))) if n else a
    qp, kp, vp = pad(q, pad_q), pad(k, pad_k), pad(v, pad_k)
    nq = qp.shape[2] // blk_q
    nk = kp.shape[2] // blk_k

    from jax.experimental.pallas import tpu as pltpu

    # a causal call without a window walks the list of the pairs it
    # computes (`_visits`): no grid step above the diagonal
    listed = causal and window is None
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        seq_len=seq,
        blk_q=blk_q,
        blk_k=blk_k,
        selected=selection is not None,
        sink=sink is not None,
        window=window,
        nk=nk,
        listed=listed,
    )

    # each grid step's (query block, key block), for the index maps
    # (`lax.div`, not `//`: the operands are never negative, and Mosaic
    # lowers an index map too: a floor division's sign handling does not
    # lower under x64)
    if listed:
        # one table, (query block, key block) a step side by side: each
        # scalar-prefetch operand's copy takes 16 KiB of a v5e program's
        # temporaries
        pairs = _visits(seq, blk_q, blk_k, None)
        tables = [jnp.asarray(np.ravel(pairs), jnp.int32)]
        grid = (batch, heads, len(pairs))
        at = lambda b, h, p, table: (table[2 * p], table[2 * p + 1])
    elif window is not None:
        tables = []
        # the key axis takes as many steps as the longest band holds blocks
        bands = np.bincount([i for i, _ in _visits(seq, blk_q, blk_k, window)])
        grid = (batch, heads, nq, int(bands.max()))

        def at(b, h, i, j):
            # the band's (first + j)-th block; past its last, the last
            # again (already there): nothing new is read
            first, last = _band(i, blk_q, blk_k, window, nk)
            return i, jnp.minimum(first + j, last)
    else:  # not causal: every block
        tables = []
        grid = (batch, heads, nq, nk)
        at = lambda b, h, i, j: (i, j)

    def q_block(b, h, *step):
        return (b, h, at(b, h, *step)[0], jnp.int32(0))

    def key_block(keys):
        group = heads // keys.shape[1]
        return lambda b, h, *step: (b, jax.lax.div(h, jnp.int32(group)),
                                    at(b, h, *step)[1], jnp.int32(0))

    operands = [qp, kp, vp]
    in_specs = [
        pl.BlockSpec((None, None, blk_q, d), q_block),
        pl.BlockSpec((None, None, blk_k, d), key_block(kp)),
        pl.BlockSpec((None, None, blk_k, dv), key_block(vp)),
    ]
    if second is not None:
        q2p, k2p = pad(second[0], pad_q), pad(second[1], pad_k)
        d2 = q2p.shape[-1]
        operands += [q2p, k2p]
        in_specs += [
            pl.BlockSpec((None, None, blk_q, d2), q_block),
            pl.BlockSpec((None, None, blk_k, d2), key_block(k2p)),
        ]
    if selection is not None:  # (batch, seq, seq): one mask for every head
        operands.append(jnp.pad(selection, ((0, 0), (0, pad_q), (0, pad_k)))
                        if pad_q or pad_k else selection)
        in_specs.append(pl.BlockSpec(
            (None, blk_q, blk_k), lambda b, h, *step: (b, *at(b, h, *step))))
    if sink is not None:  # a logit a head
        operands.append(sink.astype(jnp.float32).reshape(heads, 1, 1))
        in_specs.append(pl.BlockSpec(
            (None, 1, 1), lambda b, h, *step: (h, jnp.int32(0), jnp.int32(0))))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, None, blk_q, dv), q_block),
            scratch_shapes=[
                pltpu.VMEM((blk_q, 1), jnp.float32),  # running max
                pltpu.VMEM((blk_q, 1), jnp.float32),  # running denominator
                pltpu.VMEM((blk_q, dv), jnp.float32),  # weighted accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qp.shape[:-1] + (dv,), q.dtype),
        interpret=interpret,
    )(*tables, *operands)
    return out[:, :, :seq] if pad_q else out


_flash = jax.custom_vjp(_flash_forward, nondiff_argnums=(4, 5, 6, 7, 8, 9))


def _flash_fwd(q, k, v, second, *static):
    return _flash_forward(q, k, v, second, *static), (q, k, v, second)


def _windowed(q, k, v, scale, window):
    """Plain attention of one head over the band ``t - window < s <= t``."""
    t = jnp.arange(q.shape[0])[:, None] - jnp.arange(k.shape[0])[None, :]
    s = (q.astype(jnp.float32) @ k.astype(jnp.float32).T) * scale
    w = jax.nn.softmax(jnp.where((t >= 0) & (t < window), s, -jnp.inf), axis=-1)
    return (w @ v.astype(jnp.float32)).astype(q.dtype)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res, g):
    from ..parallel.ring import full_attention

    def attend(q, k, v):
        if window is not None:
            return _windowed(q, k, v, scale, window)
        return full_attention(q, k, v, causal=causal, scale=scale)

    def plain(q, k, v, second):
        if q.ndim == 2:
            return attend(q, k, v)
        every = lambda a: jnp.repeat(a, q.shape[1] // a.shape[1], axis=1)
        k = every(k)
        if second is not None:  # the score's two parts as one wider dot
            q = jnp.concatenate([q, second[0]], axis=-1)
            k = jnp.concatenate([k, every(second[1])], axis=-1)
        return jax.vmap(jax.vmap(attend))(q, k, every(v))

    _, vjp = jax.vjp(plain, *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def sparse_attention(
    q, k, v, selection, sink, *, q2, k2, scale: float, block: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention over a selection of keys a query, with a sink:
    `flash_attention`'s two score parts (``q kᵀ + q2 k2ᵀ``, ``k2`` with
    heads of its own count), ``selection`` (batch, seq, seq) int8, 1 where
    key s is in query t's set (one mask for every head), and ``sink``
    (heads,) a logit a head in the softmax's denominator: ``p = exp(z) /
    (exp(sink) + Σ_selected exp(z))``. Blocks of keys above the diagonal
    hold no selected key and are never visited; every other block is a
    dense pass masked by the selection. No backward pass (scoring)."""
    return _flash_call(
        q, k, v, (q2, k2), True, float(scale), block, block, bool(interpret),
        selection=selection, sink=sink)


def _index_kernel(start_ref, q_ref, k_ref, w_ref, o_ref, *, heads: int, width: int,
                  scale: float, seq_len: int, blk_q: int, blk_k: int):
    """One block of keys against the block of queries from ``start_ref[0]``:
    ``Σ_j w_j ReLU(q_j · k) * scale`` over ``heads`` heads of ``width``
    side by side in ``q_ref``; a key after the query (or past the window)
    reads `_NEG_INF`."""
    j = pl.program_id(1)
    first = start_ref[0]

    @pl.when(j * blk_k <= first + (blk_q - 1))
    def _scores():
        k = k_ref[:]
        acc = jnp.zeros((blk_q, blk_k), jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[:, h * width:(h + 1) * width], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + w_ref[:, h:h + 1] * jnp.maximum(s, jnp.float32(0.0))
        q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        keep = jnp.logical_and(q_pos >= k_pos, k_pos < seq_len)
        o_ref[:] = jnp.where(keep, acc * jnp.float32(scale), jnp.float32(_NEG_INF))

    @pl.when(j * blk_k > first + (blk_q - 1))
    def _after():
        o_ref[:] = jnp.full((blk_q, blk_k), _NEG_INF, jnp.float32)


def index_scores(
    q, k, w, start, *, scale: float, block_k: int = 512, interpret: bool = False,
) -> jax.Array:
    """A lightning indexer's scores of one block of queries against every
    key of the window: ``I[t, s] = scale * Σ_j w[t, j] ReLU(q[t, j] · k[s])``
    for ``s <= t``, `_NEG_INF` after. ``q`` (rows, block, heads, width) the
    queries from position ``start`` (int32, may be traced) on, ``k`` (rows,
    seq, width) one key a position for all heads, ``w`` (rows, block,
    heads) float32 the heads' weights. Returns (rows, block, seq) float32:
    the score matrix of the whole window is never written. Products take
    the operands' dtype, sums float32; blocks of keys after the block's last
    query are skipped."""
    from jax.experimental.pallas import tpu as pltpu

    rows, blk_q, heads, width = q.shape
    seq = k.shape[1]
    blk_k = min(block_k, max(8, seq))
    pad = (-seq) % blk_k
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0))) if pad else k
    nk = kp.shape[1] // blk_k

    def key_block(r, j, first):  # above the block's diagonal: the last needed again
        last = jax.lax.div(first[0] + (blk_q - 1), jnp.int32(blk_k))
        return (r, jnp.minimum(j, last), jnp.int32(0))

    zero = lambda r, j, first: (r, jnp.int32(0), jnp.int32(0))
    out = pl.pallas_call(
        functools.partial(_index_kernel, heads=heads, width=width, scale=float(scale),
                          seq_len=seq, blk_q=blk_q, blk_k=blk_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, nk),
            in_specs=[
                pl.BlockSpec((None, blk_q, heads * width), zero),
                pl.BlockSpec((None, blk_k, width), key_block),
                pl.BlockSpec((None, blk_q, heads), zero),
            ],
            out_specs=pl.BlockSpec(
                (None, blk_q, blk_k), lambda r, j, first: (r, jnp.int32(0), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, blk_q, nk * blk_k), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), q.reshape(rows, blk_q, heads * width),
      kp, w.astype(jnp.float32))
    return out[..., :seq] if pad else out


def _top_k_kernel(start_ref, s_ref, kth_ref, tied_ref, keys_ref, *, k: int, fold: int):
    """A tile of query rows (rows, width) of index scores: the k-th largest
    score a row, the index of its last kept tie and the kept keys in
    ascending order (`index_top_k`)."""
    from jax.experimental.pallas import tpu as pltpu

    f32, i32 = jnp.float32, jnp.int32
    rows, width = s_ref.shape
    first = start_ref[0] + pl.program_id(1) * rows
    t = first + jax.lax.broadcasted_iota(i32, (rows, 1), 0)

    @pl.when(first + rows <= k)
    def _every_key():  # no query of the tile has more than k causal keys
        kth_ref[:] = jnp.full((rows, 1), -jnp.inf, f32)
        tied_ref[:] = jnp.full((rows, 1), -1, i32)
        key = jax.lax.broadcasted_iota(i32, (rows, fold), 1)
        keys_ref[:] = jnp.where(key <= t, key, i32(-1))

    @pl.when(first + rows > k)
    def _threshold():
        lane = jax.lax.broadcasted_iota(i32, (rows, width), 1)
        x = s_ref[:]
        # the scores' signed-int32 image, in their order (-0.0 is +0.0 first)
        bits = jax.lax.bitcast_convert_type(jnp.where(x == f32(0.0), f32(0.0), x), i32)
        img = jnp.where(bits >= 0, bits, bits ^ i32(0x7FFFFFFF))
        count = lambda keep: jnp.sum(jnp.where(keep, f32(1.0), f32(0.0)), axis=1, keepdims=True)

        def bisect(i, found):  # the largest threshold that k scores reach, a bit at a time
            trial = found | jax.lax.shift_left(i32(1), i32(31) - i)
            return jnp.where(count(img >= (trial ^ i32(-2**31))) >= f32(k), trial, found)

        kth = jax.lax.fori_loop(i32(0), i32(32), bisect, jnp.zeros((rows, 1), i32)) ^ i32(-2**31)
        above, tie = img > kth, img == kth
        need = i32(k) - count(above).astype(i32)  # the ties kept: the first `need`
        # one prefix count of both: ties in the low bits, scores above in the high
        low = width.bit_length()
        own = jnp.where(tie, i32(1), i32(0)) + jnp.where(above, i32(1 << low), i32(0))
        run, d = own, 1
        while d < width:
            run = run + jnp.where(lane >= d, pltpu.roll(run, i32(d), 1), i32(0))
            d *= 2
        run = run - own
        ties_before = run & i32((1 << low) - 1)
        kept_tie = tie & (ties_before < need)
        kept = (above | kept_tie) & (lane <= t)
        tied = jnp.max(jnp.where(kept_tie, lane.astype(f32), f32(-1.0)), axis=1, keepdims=True)
        # each kept key moves left by the unkept keys before it, a bit of that
        # distance a step from the lowest: none lands on another, and after
        # the bits below `fold` a key stands at its place in the list modulo
        # `fold`
        gaps = lane - ((run >> low) + jnp.minimum(ties_before, need))
        moving = jnp.where(kept, ((gaps & i32(fold - 1)) << low) | (lane + 1), i32(0))
        d = 1
        while d < fold:
            step = low + d.bit_length() - 1
            came = pltpu.roll(moving, i32(width - d), 1)
            moving = jnp.where(((came >> step) & 1) == 1, came,
                               jnp.where(((moving >> step) & 1) == 1, i32(0), moving))
            d *= 2
        listed = moving[:, :fold]
        for part in range(1, width // fold):
            listed = listed | moving[:, part * fold:(part + 1) * fold]
        keys_ref[:] = (listed & i32((1 << low) - 1)) - 1
        kth_ref[:] = jax.lax.bitcast_convert_type(
            jnp.where(kth >= 0, kth, kth ^ i32(0x7FFFFFFF)), f32)
        tied_ref[:] = tied.astype(i32)


def index_top_k(scores, start, *, k: int, interpret: bool = False):
    """The ``k`` largest of each query's index scores, exactly, without a
    sort. ``scores`` (rows, block, seq) float32, a block of queries from
    position ``start`` (int32, may be traced) on, as `index_scores` gives
    them (`_NEG_INF` after the query). Returns (kth (rows, block, 1)
    float32, the k-th largest score a query; tied (rows, block, 1) int32,
    the index of the last score equal to it that is kept, ties kept from
    the lowest index on; keys (rows, block, k) int32, the kept keys ``s <=
    t`` in ascending order, -1 after). The set is ``lax.top_k``'s, the
    scores compared as IEEE values (-0.0 equal to +0.0). A tile of 32
    queries' whole rows is in VMEM: the threshold is 32 counts of a
    bisection on the scores' bits, the ties' ranks and the keys' places one
    prefix count, and the list a compaction of log2(k) lane shifts. A tile
    whose last query is below ``k`` keeps every causal key and selects
    nothing (kth -inf, tied -1)."""
    rows, block, seq = scores.shape
    fold = max(128, 1 << (int(k) - 1).bit_length())
    width = -(-seq // fold) * fold
    if width.bit_length() + fold.bit_length() > 31:
        raise ValueError(f"a window of {seq} keys is too long for one tile row here")
    if width > seq:  # never kept: below every score of the window
        scores = jnp.pad(scores, ((0, 0), (0, 0), (0, width - seq)),
                         constant_values=-jnp.inf)
    # 32 rows a tile: a block of 1,024 queries in 2.2 ms, 2.84 at 8 (host clock, TPU v5e)
    tile = next(n for n in (32, 8, block) if block % n == 0)
    from jax.experimental.pallas import tpu as pltpu

    per_row = lambda n: pl.BlockSpec((None, tile, n), lambda r, i, first: (r, i, jnp.int32(0)))
    kth, tied, keys = pl.pallas_call(
        functools.partial(_top_k_kernel, k=int(k), fold=fold),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, block // tile),
            in_specs=[per_row(width)],
            out_specs=[per_row(1), per_row(1), per_row(fold)],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, block, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, block, 1), jnp.int32),
                   jax.ShapeDtypeStruct((rows, block, fold), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), scores)
    return kth, tied, keys[..., :k]


def _ssd_kernel(
    x_ref, dt_ref, acol_ref, arow_ref, bt_ref, c_ref, d_ref, y_ref, state,
    *, heads: int, width: int,
):
    """One chunk of one (row, group): ``heads`` heads of ``width`` side by
    side in ``x_ref`` (chunk, heads * width). ``acol`` / ``arow`` hold the
    chunk's running sum of Δ·A, positions down (chunk, heads) and across
    (heads, chunk); ``bt`` is B transposed (state, chunk)."""
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[:] = jnp.zeros_like(state)

    c, bt = c_ref[:], bt_ref[:]
    chunk = c.shape[0]
    # C_t · B_s, shared by the group's heads
    scores = jnp.dot(c, bt, preferred_element_type=f32)
    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = t >= s
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    for h in range(heads):
        lo = h * width
        x = x_ref[:, lo:lo + width]
        dtype = x.dtype
        a_col = acol_ref[:, h:h + 1]  # (chunk, 1): sum of Δ·A up to t
        a_row = arow_ref[h:h + 1, :]  # (1, chunk): the same, up to s
        # differences are <= 0 where t >= s: nothing overflows
        decay = jnp.exp(jnp.where(causal, a_col - a_row, f32(_NEG_INF)))
        xdt = (dt_ref[:, h:h + 1] * x.astype(f32)).astype(dtype)
        held = state[h]  # (state, width) float32
        y = jnp.dot((scores * decay).astype(dtype), xdt, preferred_element_type=f32)
        y = y + jnp.exp(a_col) * jnp.dot(
            c, held.astype(dtype), preferred_element_type=f32)
        y = y + d_ref[:, h:h + 1] * x.astype(f32)
        y_ref[:, lo:lo + width] = y.astype(y_ref.dtype)
        # the chunk's sum, (1, 1): a masked sum (a one-lane slice does not
        # broadcast over sublanes and lanes on the chip)
        a_end = jnp.sum(jnp.where(last, a_row, f32(0.0)), axis=-1, keepdims=True)
        after = jnp.exp(a_end - a_row)  # the decay from s to the chunk's end
        state[h] = jnp.exp(a_end) * held + jnp.dot(
            (bt.astype(f32) * after).astype(dtype), xdt, preferred_element_type=f32)


def _ssd_operands(x, dt, A, B, C, chunk):
    """Padded to whole chunks (Δ = 0: a padded position decays nothing and
    adds nothing) and laid out a group at a time: ``x`` (rows, seq, heads
    * width), Δ and the running sum of Δ·A inside each chunk (rows, groups,
    seq, heads a group), B and C (rows, seq, groups, state)."""
    rows, seq, heads, width = x.shape
    groups = B.shape[2]
    if heads % groups:
        raise ValueError(f"{heads} heads do not divide over {groups} groups")
    per = heads // groups
    pad = (-seq) % chunk
    if pad:
        grow = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C = grow(x), grow(dt), grow(B), grow(C)
    n = (seq + pad) // chunk
    dt = dt.astype(jnp.float32)
    da = dt * A.astype(jnp.float32)
    run = jnp.cumsum(da.reshape(rows, n, chunk, heads), axis=2).reshape(dt.shape)
    by_group = lambda a: jnp.swapaxes(a.reshape(rows, seq + pad, groups, per), 1, 2)
    return x.reshape(rows, seq + pad, heads * width), by_group(dt), by_group(run), B, C


def ssd_scan(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
    D: jax.Array, *, chunk: int = 128, interpret: bool = False,
) -> jax.Array:
    """The state-space scan ``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t``,
    ``y_t = S_t C_t + D x_t`` from ``S_0 = 0``, a chunk of ``chunk``
    positions at a time. ``x`` (rows, seq, heads, width); ``dt`` (rows,
    seq, heads) float32, Δ after its softplus; ``A``, ``D`` (heads,), A
    negative; ``B``, ``C`` (rows, seq, groups, state), head ``j`` using
    group ``j // (heads // groups)``. ``y`` has ``x``'s shape and dtype.
    Matmul operands are in ``x``'s dtype, sums, decays and the state in
    float32. On the chip ``chunk`` is a multiple of 128 (or the whole
    sequence); the function refuses to be differentiated."""
    return _ssd(x, dt, A, B, C, D, int(chunk), bool(interpret))


def _ssd_forward(x, dt, A, B, C, D, chunk, interpret):
    from jax.experimental.pallas import tpu as pltpu

    rows, seq, heads, width = x.shape
    groups, size = B.shape[2], B.shape[3]
    per = heads // groups
    xg, dtg, run, B, C = _ssd_operands(x, dt, A, B, C, chunk)
    padded = xg.shape[1]
    # int32 zeros made inside the index maps (x64 would make a plain 0 int64)
    across = pl.BlockSpec(
        (None, None, chunk, per), lambda r, g, c: (r, g, c, jnp.int32(0)))
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, heads=per, width=width),
        grid=(rows, groups, padded // chunk),
        in_specs=[
            pl.BlockSpec((None, chunk, per * width), lambda r, g, c: (r, c, g)),
            across, across,
            pl.BlockSpec((None, None, per, chunk), lambda r, g, c: (r, g, jnp.int32(0), c)),
            pl.BlockSpec((None, None, size, chunk), lambda r, g, c: (r, g, jnp.int32(0), c)),
            pl.BlockSpec((None, chunk, size), lambda r, g, c: (r, c, g)),
            pl.BlockSpec((None, 1, per), lambda r, g, c: (g, jnp.int32(0), jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((None, chunk, per * width), lambda r, g, c: (r, c, g)),
        out_shape=jax.ShapeDtypeStruct(xg.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((per, size, width), jnp.float32)],  # the state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(
        xg, dtg, run, jnp.swapaxes(run, 2, 3),
        jnp.transpose(B, (0, 2, 3, 1)).astype(x.dtype),
        C.reshape(rows, padded, groups * size).astype(x.dtype),
        D.astype(jnp.float32).reshape(groups, 1, per),
    )
    return out[:, :seq].reshape(x.shape)


_ssd = jax.custom_vjp(_ssd_forward, nondiff_argnums=(6, 7))


def _ssd_fwd(*args):
    raise NotImplementedError(
        "ssd_scan has no backward pass: the chunked scan is a forward kernel "
        "(scoring)"
    )


_ssd.defvjp(_ssd_fwd, lambda *a: None)


def _head_kernel(x_ref, w_ref, t_ref, o_ref, m_sc, l_sc, g_sc, *, blk_v: int, vocab: int):
    """One (token tile, vocabulary tile) step: the tile's logits ``x · w``
    in float32, folded into the running max, the running sum of
    ``exp(s - max)`` and the target's logit; the last step writes
    ``target logit - (max + log sum)``."""
    f32 = jnp.float32
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        g_sc[:] = jnp.zeros_like(g_sc)

    s = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=f32)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    v0 = j * jnp.int32(blk_v)
    if vocab % blk_v:  # the last tile runs past the vocabulary: its tail is no logit
        s = jnp.where(col < vocab - v0, s, f32(_NEG_INF))
    m_prev = m_sc[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_sc[:] = jnp.exp(m_prev - m_new) * l_sc[:] + jnp.sum(
        jnp.exp(s - m_new), axis=1, keepdims=True)
    m_sc[:] = m_new
    g_sc[:] += jnp.sum(jnp.where(col == t_ref[:] - v0, s, f32(0.0)), axis=1, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[:] = g_sc[:] - (m_sc[:] + jnp.log(l_sc[:]))


def head_tiles(n: int, d: int, vocab: int, itemsize: int = 2):
    """(block_t, block_v) for `head_logprob` over ``n`` tokens of width
    ``d`` and ``vocab`` ids: a token tile of up to 1,024 (``w`` is read
    once a token tile, so the tile sets the bytes read a FLOP), and the
    widest multiple of 128 from 1,024 on that divides ``vocab`` with a
    tile of ``w`` within 12 MiB and the tile's float32 logits within
    9 MiB; where none divides, 1,024, its last tile masked. A vocabulary
    under 1,024 is one tile. On a v5e at 32,768 tokens (d = 2,048) the
    kernel ran at 92-94% of the bf16 peak with such tiles, and at 83%
    with 2,944 columns (12 MiB of logits a tile)."""
    block_t = min(1024, -(-n // 16) * 16)
    if vocab <= 1024:
        return block_t, vocab
    fit = min((12 << 20) // (d * itemsize), (9 << 20) // (block_t * 4)) // 128 * 128
    even = [b for b in range(fit, 1023, -128) if vocab % b == 0]
    return block_t, even[0] if even else 1024


def head_logprob(
    x: jax.Array, w: jax.Array, target: jax.Array, *,
    block_t: Optional[int] = None, block_v: Optional[int] = None, interpret: bool = False,
) -> jax.Array:
    """Each token's log-probability of its target under a language-model
    head, ``x_i · w[:, t_i] - logsumexp_j(x_i · w[:, j])``, without the
    logits ever leaving VMEM. ``x`` (n, d) in ``w``'s dtype, ``w`` (d, V),
    ``target`` (n,) int32 ids below V; returns (n,) float32.

    The grid is (token tiles, vocabulary tiles): a token tile's ``x`` stays
    in VMEM while the vocabulary tiles of ``w`` stream past it (the token
    axis parallel, the vocabulary axis innermost and sequential). A step
    computes its tile of logits with ``x``'s dtype on the MXU and float32
    sums (the products and sums of ``jnp.dot(..., preferred_element_type=
    float32)``), updates the running max and sum of exponentials online
    and picks the target's logit by comparing ``target - v0`` with the
    tile's column iota: no gather, no reshape. Columns past V in the last
    tile are masked to -1e30; tokens past ``n`` are padded and dropped.
    Tiles default to `head_tiles`; the VMEM limit is raised to what two
    buffers of each input tile and three float32 logit tiles take, from
    32 MiB up to 100 MiB of a v5e's 128 (57 MiB at 1,024 x 2,176, d =
    2,048). No backward pass (scoring)."""
    n, d = x.shape
    vocab = w.shape[1]
    auto_t, auto_v = head_tiles(n, d, vocab, jnp.dtype(w.dtype).itemsize)
    blk_t, blk_v = int(block_t or auto_t), int(block_v or auto_v)
    blk_v = min(blk_v, vocab)
    pad = (-n) % blk_t
    x = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    t = target.astype(jnp.int32).reshape(n, 1)
    t = jnp.pad(t, ((0, pad), (0, 0))) if pad else t
    from jax.experimental.pallas import tpu as pltpu

    item = jnp.dtype(w.dtype).itemsize
    vmem = 2 * (blk_t * d + d * blk_v) * item + 3 * blk_t * blk_v * 4 + (4 << 20)
    out = pl.pallas_call(
        functools.partial(_head_kernel, blk_v=blk_v, vocab=vocab),
        grid=((n + pad) // blk_t, -(-vocab // blk_v)),
        in_specs=[
            pl.BlockSpec((blk_t, d), lambda i, j: (i, jnp.int32(0))),
            pl.BlockSpec((d, blk_v), lambda i, j: (jnp.int32(0), j)),
            pl.BlockSpec((blk_t, 1), lambda i, j: (i, jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((blk_t, 1), lambda i, j: (i, jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((n + pad, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((blk_t, 1), jnp.float32) for _ in range(3)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(min(max(vmem, 32 << 20), 100 << 20))),
        interpret=interpret,
    )(x, w, t)
    return out[:n, 0]
