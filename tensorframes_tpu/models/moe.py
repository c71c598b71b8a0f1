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
still routed over all experts. A layer that holds a SHARE of the experts
(``experts``, the router's width, above the held count) does its gathers
and matmuls over the rows routed to its own experts alone: the sort puts
the rows held elsewhere last, and a loop whose trip count is the held
rows' takes the sorted order `STEP_ROWS` at a time and adds each row's
weighted output to its token's (no place is kept for a routed row, so the
layer is not taken in parts).
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

__all__ = ["MoEFFN", "route", "held_experts", "parts_for", "activation"]

# the most that one part's routed rows may take in temporaries (the
# gathered rows, the up projection's float32 output and the activation
# beside it, or the down projection's output and its copy in token order)
PART_BYTES = 3 << 30
# sorted rows a step of a held share's loop multiplies (the last step may
# reach into the rows held elsewhere: they are in no group)
STEP_ROWS = 8192


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
    temporaries (a routed row's ``d`` inputs, the experts' input width,
    ``up`` float32 outputs of the up projection and ``f`` activations; or
    ``f`` activations, ``d`` float32 outputs and their copy in token
    order) within `PART_BYTES`."""
    row_bytes = max(d * itemsize + 4 * up + f * itemsize, f * itemsize + 8 * d)
    least = -(-rows * k * row_bytes // PART_BYTES)
    return next(n for n in range(max(1, least), rows + 1) if rows % n == 0)


def activation(act: str, h, limit: Optional[float] = None):
    """An FFN's activation over its up projection ``h``: "swiglu" (gate
    and up side by side, ``silu(gate) * up``; with ``limit``, a clamped
    SwiGLU ``silu(min(gate, limit)) * clip(up, -limit, limit)``), "relu2"
    (``relu(h)**2``) or "gelu", the last two over one matrix's output."""
    if act == "swiglu":
        f = h.shape[-1] // 2
        if limit is None:
            return jax.nn.silu(h[..., :f]) * h[..., f:]
        return jax.nn.silu(jnp.minimum(h[..., :f], limit)) * jnp.clip(h[..., f:], -limit, limit)
    if act == "relu2":
        return jnp.square(jax.nn.relu(h))
    if act == "gelu":
        return jax.nn.gelu(h)
    raise ValueError(f"activation {act!r}: 'swiglu', 'relu2' or 'gelu'")


def held_experts(
    u, idx, weight, w_up, w_down, held, *, act: str = "swiglu", layer=None,
    experts: Optional[int] = None, limit: Optional[float] = None,
):
    """The held experts' part of the layer's output, ``(rows, d)`` float32.

    ``u`` (rows, d) are the experts' inputs, ``idx`` / ``weight`` the
    router's choice over ALL experts. ``w_up`` is ``(count, d, 2f)``
    (gate and up projections side by side: ``act`` "swiglu") or
    ``(count, d, f)`` ("relu2", "gelu"), ``w_down`` ``(count, f, d)``;
    expert ``e`` of the model is held at ``e - first``. ``first`` may be
    traced (a shard's index). ``experts``, the router's width, says that
    ``count`` below it is a share: the gathers and matmuls then run over
    the rows routed to the held experts alone (the sorted order
    `STEP_ROWS` at a time, as many steps as those rows take, their outputs
    added to their tokens'); without it
    the layer may hold everything and multiplies every routed row's
    place. With ``layer`` (may be traced) the weights are the
    stacks of several layers, ``(layers, count, ...)``: the grouped matmul
    takes every layer's experts as its groups and the other layers' are
    empty, so no layer's weights are copied out of their stack."""
    first, count = held
    rows, k = idx.shape
    d = u.shape[-1]
    share = experts is not None and count < experts
    if layer is not None:
        w_up = w_up.reshape((-1,) + w_up.shape[2:])
        w_down = w_down.reshape((-1,) + w_down.shape[2:])

    def grouped(idx):
        """(which routed rows are held here, `group_rows` of them by held
        expert): the rows held elsewhere go last."""
        local = idx.reshape(-1).astype(jnp.int32) - jnp.asarray(first, jnp.int32)
        mine = (local >= 0) & (local < count)
        return (mine,) + tuple(group_rows(jnp.where(mine, local, count), count))

    def multiply(u, places, sizes):
        """The two grouped matmuls over the sorted rows at ``places``."""
        if layer is not None:  # this layer's groups among every layer's
            at = jnp.asarray(layer, jnp.int32) * jnp.int32(count)
            sizes = lax.dynamic_update_slice(
                jnp.zeros(w_up.shape[0], jnp.int32), sizes, (at,)
            )
        xs = jnp.take(u, places // k, axis=0)
        h = lax.ragged_dot(xs, w_up, sizes, preferred_element_type=jnp.float32)
        return lax.ragged_dot(
            activation(act, h, limit).astype(u.dtype), w_down, sizes,
            preferred_element_type=jnp.float32,
        )

    def held_rows(u, idx, weight):
        """The part of the held experts, over their rows alone: the first
        of the sorted order, `STEP_ROWS` places a step, each step's groups
        the part of every expert's rows that lies in it, each row's
        weighted output added to its token's. No place is kept for a
        routed row: the layer needs no parts."""
        i32 = jnp.int32
        _, order, _, sizes = grouped(idx)
        n = order.shape[0]
        step = min(STEP_ROWS, n)
        order = jnp.pad(order, (0, (-n) % step))
        ends = jnp.cumsum(sizes, dtype=i32)
        starts = ends - sizes
        flat = weight.reshape(-1).astype(jnp.float32)

        def one(c, out):
            lo = c * i32(step)
            within = jnp.clip(ends, lo, lo + step) - jnp.clip(starts, lo, lo + step)
            places = lax.dynamic_slice(order, (lo,), (step,))
            y = multiply(u, places, within)
            # the last step reaches into the rows held elsewhere: in no group
            w = jnp.where(lo + lax.iota(i32, step) < ends[-1], jnp.take(flat, places), 0.0)
            return out.at[places // k].add(jnp.where(w[:, None] != 0.0, y * w[:, None], 0.0))

        steps = lax.div(ends[-1] + i32(step - 1), i32(step))
        return lax.fori_loop(
            i32(0), steps, one, jnp.zeros((rows, w_down.shape[-1]), jnp.float32))

    def part(args):
        u, idx, weight = args
        mine, order, back, sizes = grouped(idx)
        y = multiply(u, order, sizes)
        # back to (row, choice) order; a row no held expert computed is 0
        y = jnp.take(y, back, axis=0).reshape(idx.shape[0], k, -1)
        w = jnp.where(mine.reshape(idx.shape), weight, 0.0)
        return jnp.sum(jnp.where(w[..., None] != 0.0, y * w[..., None], 0.0), axis=1)

    with jax.named_scope("moe.experts"):
        if share:
            return held_rows(u, idx, weight)
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
            held or (0, self.num_experts), act="gelu",
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
