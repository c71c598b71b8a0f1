"""Mixture-of-experts FFN: a router over all experts, and the part of the
result that the experts HELD HERE give.

An expert layer is told which experts it holds, ``held = (first,
count)``: the router scores and chooses over all ``num_experts`` (its
published width), the routed rows are grouped by expert (one sort), and a
grouped matmul (`jax.lax.ragged_dot`: on the TPU one kernel that visits
each expert's own rows, no expert on a row not routed to it) computes
what the held experts give. No token is dropped, there is no capacity.
Rows routed to experts held elsewhere add nothing here: their part is
another holder's, and the parts of all holders add up to the whole layer
(`apply_ep`: each shard calls the same local function with its own
``held`` and one `psum` adds the parts). On one chip that holds every
expert, ``held = (0, num_experts)`` and the layer is whole.

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

__all__ = ["MoEFFN", "route", "held_experts"]


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


def held_experts(
    u, idx, weight, w_up, w_down, held, *, gated: bool = True,
):
    """The held experts' part of the layer's output, ``(rows, d)`` float32.

    ``u`` (rows, d) are the normed inputs, ``idx`` / ``weight`` the
    router's choice over ALL experts. ``w_up`` is ``(count, d, 2f)``
    (gate and up projections side by side: SwiGLU, ``gated``) or
    ``(count, d, f)`` (GELU), ``w_down`` ``(count, f, d)``; expert ``e``
    of the model is held at ``e - first``. ``first`` may be traced (a
    shard's index)."""
    first, count = held
    rows, k = idx.shape
    with jax.named_scope("moe.experts"):
        local = idx.reshape(-1).astype(jnp.int32) - jnp.asarray(first, jnp.int32)
        mine = (local >= 0) & (local < count)
        key = jnp.where(mine, local, count)  # rows held elsewhere go last
        # a counting sort (the keys are few): a routed row's place is its
        # expert's offset plus its rank among that expert's rows
        # (int32 said everywhere: under x64 a sum of int32 is int64)
        i32 = jnp.int32
        hot = (key[:, None] == jnp.arange(count + 1, dtype=i32)).astype(i32)
        sizes = jnp.sum(hot, axis=0, dtype=i32)
        rank = jnp.cumsum(hot, axis=0, dtype=i32) - hot
        offsets = jnp.cumsum(sizes, dtype=i32) - sizes
        back = jnp.sum(hot * (rank + offsets), axis=1, dtype=i32)
        order = jnp.zeros_like(back).at[back].set(
            jnp.arange(back.shape[0], dtype=i32), unique_indices=True
        )
        sizes = sizes[:count]
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
        y = jnp.take(y, back, axis=0).reshape(rows, k, -1)
        w = jnp.where(mine.reshape(rows, k), weight, 0.0)
        return jnp.sum(jnp.where(w[..., None] != 0.0, y * w[..., None], 0.0), axis=1)


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
