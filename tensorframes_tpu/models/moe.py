"""Mixture-of-experts FFN: a router over all experts, and the part of the
result that the experts HELD HERE give.

An expert layer is told which experts it holds, ``held = (first,
count)``: the router scores and chooses over all ``num_experts`` (its
published width), the routed rows are grouped by expert (one stable sort
of the rows' keys: its cost does not grow with the number of experts),
and a grouped matmul (`jax.lax.ragged_dot`: on the TPU one kernel that
visits each expert's own rows, no expert on a row not routed to it)
computes what the held experts give. No token is dropped, there is no
capacity. The layer takes its tokens in parts where the routed rows'
temporaries would pass `PART_BYTES` (`parts_for`: from the routed rows
and the experts' widths alone), each part grouped and multiplied by
itself, so that a long block fits beside the weights; every token is
still routed over all experts.
Rows routed to experts held elsewhere add nothing here: their part is
another holder's, and the parts of all holders add up to the whole layer
(`apply_ep`: each shard calls the same local function with its own
``held`` and one `psum` adds the parts). On one chip that holds every
expert, ``held = (0, num_experts)`` and the layer is whole. What every
holder would compute alike, a shared expert, is not in here: the caller
adds it once, after the parts (`models.lm`).

Router options (a configuration's keys): ``score`` "sigmoid" (scores are
sigmoids of the router logits; the top-k are chosen by score plus an
optional per-expert bias, weighted by the scores themselves, optionally
normalised to sum 1, times a scaling factor) or "softmax_topk" (softmax
over the chosen top-k logits). Scores are float32 whatever the
activations' dtype.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["MoEFFN", "route", "held_experts", "parts_for"]

# the most that one part's routed rows may take in temporaries (the
# gathered rows, the up projection's float32 output and the activation
# beside it, or the down projection's output and its copy in token order)
PART_BYTES = 3 << 30


def _along_rows(x, idx):
    """``x[i, idx[i, j]]`` with int32 indices throughout
    (`take_along_axis` makes them int64 under x64, and the chip's grouped
    matmul compiles in no module that holds a 64-bit array)."""
    rows, width = x.shape
    flat = jnp.arange(rows, dtype=jnp.int32)[:, None] * width + idx.astype(jnp.int32)
    return jnp.take(x.reshape(-1), flat, axis=0)


def route(
    u, router, bias=None, *, top_k: int, score: str = "sigmoid",
    norm_topk: bool = True, scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Which experts each row goes to, and with what weight:
    ``(idx (rows, top_k) int32, weight (rows, top_k) float32)``."""
    with jax.named_scope("moe.route"):
        # float32 operands at full precision: a rounded input flips the
        # k-th and (k+1)-th expert of a token whose scores are close
        logits = jnp.dot(
            u.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        if score == "softmax_topk":
            topv, idx = lax.top_k(logits, top_k)
            return idx.astype(jnp.int32), jax.nn.softmax(topv, axis=-1)
        if score != "sigmoid":
            raise ValueError(f"router score {score!r}: 'sigmoid' or 'softmax_topk'")
        s = jax.nn.sigmoid(logits)
        choose = s if bias is None else s + bias.astype(jnp.float32)
        _, idx = lax.top_k(choose, top_k)
        w = _along_rows(s, idx)
        if norm_topk:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), w * jnp.float32(scale)


def group_rows(key, count):
    """Routed rows grouped by ``key`` (0..count; ``count`` = held
    elsewhere, last) in their own order within a group: ``(order, back,
    sizes)``, the row at each grouped place, each row's grouped place, and
    the rows of each of the ``count`` groups. One stable sort; int32
    throughout (under x64 a sum of int32 is int64)."""
    i32 = jnp.int32
    n = key.shape[0]
    places = lax.iota(i32, n)
    grouped, order = lax.sort((key.astype(i32), places), num_keys=1, is_stable=True)
    back = jnp.zeros_like(order).at[order].set(places, unique_indices=True)
    # where each group starts among the sorted keys: a binary search a group
    starts = jnp.searchsorted(
        grouped, jnp.arange(count + 1, dtype=i32), side="left", method="scan"
    ).astype(i32)
    return order, back, starts[1:] - starts[:-1]


def parts_for(rows: int, k: int, d: int, up: int, f: int, itemsize: int) -> int:
    """In how many equal parts an expert layer takes ``rows`` tokens of
    ``k`` experts each: the fewest that divide ``rows`` and keep a part's
    temporaries (a routed row's ``d`` inputs, ``up`` float32 outputs of
    the up projection and ``f`` activations; or ``f`` activations, ``d``
    float32 outputs and their copy in token order) within `PART_BYTES`."""
    row_bytes = max(d * itemsize + 4 * up + f * itemsize, f * itemsize + 8 * d)
    least = -(-rows * k * row_bytes // PART_BYTES)
    return next(n for n in range(max(1, least), rows + 1) if rows % n == 0)


def held_experts(
    u, idx, weight, w_up, w_down, held, *, gated: bool = True, layer=None,
):
    """The held experts' part of the layer's output, ``(rows, d)`` float32.

    ``u`` (rows, d) are the normed inputs, ``idx`` / ``weight`` the
    router's choice over ALL experts. ``w_up`` is ``(count, d, 2f)``
    (gate and up projections side by side: SwiGLU, ``gated``) or
    ``(count, d, f)`` (GELU), ``w_down`` ``(count, f, d)``; expert ``e``
    of the model is held at ``e - first``. ``first`` may be traced (a
    shard's index). With ``layer`` (may be traced) the weights are the
    stacks of several layers, ``(layers, count, ...)``: the grouped matmul
    takes every layer's experts as its groups and the other layers' are
    empty, so no layer's weights are copied out of their stack."""
    first, count = held
    rows, k = idx.shape
    d = u.shape[-1]
    if layer is not None:
        w_up = w_up.reshape((-1,) + w_up.shape[2:])
        w_down = w_down.reshape((-1,) + w_down.shape[2:])

    def part(args):
        u, idx, weight = args
        local = idx.reshape(-1).astype(jnp.int32) - jnp.asarray(first, jnp.int32)
        mine = (local >= 0) & (local < count)
        # rows held elsewhere go last
        order, back, sizes = group_rows(jnp.where(mine, local, count), count)
        if layer is not None:  # this layer's groups among every layer's
            at = jnp.asarray(layer, jnp.int32) * jnp.int32(count)
            sizes = lax.dynamic_update_slice(
                jnp.zeros(w_up.shape[0], jnp.int32), sizes, (at,)
            )
        xs = jnp.take(u, order // k, axis=0)
        h = lax.ragged_dot(xs, w_up, sizes, preferred_element_type=jnp.float32)
        if gated:
            f = w_up.shape[-1] // 2
            a = jax.nn.silu(h[:, :f]) * h[:, f:]
        else:
            a = jax.nn.gelu(h)
        y = lax.ragged_dot(
            a.astype(u.dtype), w_down, sizes, preferred_element_type=jnp.float32
        )
        # back to (row, choice) order; a row no held expert computed is 0
        y = jnp.take(y, back, axis=0).reshape(idx.shape[0], k, -1)
        w = jnp.where(mine.reshape(idx.shape), weight, 0.0)
        return jnp.sum(jnp.where(w[..., None] != 0.0, y * w[..., None], 0.0), axis=1)

    with jax.named_scope("moe.experts"):
        n = parts_for(
            rows, k, d, w_up.shape[-1], w_down.shape[-2], u.dtype.itemsize
        )
        if n == 1:
            return part((u, idx, weight))
        split = lambda a: a.reshape((n, rows // n) + a.shape[1:])
        return lax.map(part, (split(u), split(idx), split(weight))).reshape(rows, d)


class MoEFFN:
    """Top-k gated expert FFNs: x -> sum_k gate_k * FFN_{e_k}(x), the
    softmax-of-top-k router over GELU two-matrix experts."""

    def __init__(
        self,
        d_model: int = 32,
        d_hidden: int = 64,
        num_experts: int = 8,
        top_k: int = 2,
        seed: int = 0,
    ):
        self.d_model, self.d_hidden = d_model, d_hidden
        self.num_experts, self.top_k = num_experts, top_k
        key = jax.random.PRNGKey(seed)
        kg, k1, k2 = jax.random.split(key, 3)
        s1 = 1.0 / np.sqrt(d_model)
        s2 = 1.0 / np.sqrt(d_hidden)
        self.params = {
            "gate": jax.random.normal(kg, (d_model, num_experts), jnp.float32) * s1,
            "w1": jax.random.normal(
                k1, (num_experts, d_model, d_hidden), jnp.float32
            ) * s1,
            "w2": jax.random.normal(
                k2, (num_experts, d_hidden, d_model), jnp.float32
            ) * s2,
        }

    def _choose(self, gate, x):
        return route(x, gate, top_k=self.top_k, score="softmax_topk")

    def _route(self, params, x):
        """Top-k softmax routing weights, (tokens, experts), rows sum to 1
        over the selected experts."""
        idx, w = self._choose(params["gate"], x)
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.zeros((x.shape[0], self.num_experts), w.dtype).at[rows, idx].add(w)

    def apply(self, params, x, held: Optional[Tuple[int, int]] = None):
        """The part of the layer that the experts ``held`` give (default:
        all of them, the whole layer); ``params`` holds those experts."""
        idx, w = self._choose(params["gate"], x)
        return held_experts(
            x, idx, w, params["w1"], params["w2"],
            held or (0, self.num_experts), gated=False,
        )

    def apply_ep(self, params, x, mesh: Mesh, axis: str = "model"):
        """Expert-parallel: experts sharded over ``axis``; one psum."""
        n_shard = mesh.shape[axis]
        if self.num_experts % n_shard:
            raise ValueError(
                f"num_experts {self.num_experts} must divide the "
                f"{axis!r} axis size {n_shard}"
            )
        e_per = self.num_experts // n_shard

        def shard_body(w1, w2, gate, xs):
            held = (lax.axis_index(axis) * e_per, e_per)
            part = self.apply({"gate": gate, "w1": w1, "w2": w2}, xs, held)
            return lax.psum(part, axis)

        espec = P(axis)
        return shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(espec, espec, P(), P()),
            out_specs=P(),
            check_vma=False,
        )(params["w1"], params["w2"], params["gate"], x)
