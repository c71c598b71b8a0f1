"""Long-context transformer LM built on the framework's parallel layer.

Demonstrates the sequence-parallel path end to end: attention runs as
`parallel.ring.ring_attention` — sequence sharded over the mesh's
``data`` axis, K/V rotating over ICI — so context length scales with the
number of chips (peak activation memory per chip is O(seq/ndev)).
Without a mesh it falls back to full attention on one device.

Kept deliberately small (pre-LN, learned positions, SGD) — it is the
framework's long-context *capability* witness, not a SOTA recipe.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..parallel.ring import _ring_shard, full_attention, ring_attention

__all__ = ["TransformerLM"]


def _layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


class TransformerLM:
    def __init__(
        self,
        vocab: int = 128,
        d_model: int = 64,
        n_heads: int = 4,
        n_layers: int = 2,
        max_seq: int = 1024,
        seed: int = 0,
    ):
        if d_model % n_heads:
            raise ValueError("d_model must divide n_heads")
        self.vocab, self.d_model = vocab, d_model
        self.n_heads, self.n_layers = n_heads, n_layers
        self.head_dim = d_model // n_heads
        key = jax.random.PRNGKey(seed)

        def init(key, shape, scale):
            return jax.random.normal(key, shape, jnp.float32) * scale

        keys = iter(jax.random.split(key, 4 + 6 * n_layers))
        p: Dict[str, jax.Array] = {
            "embed": init(next(keys), (vocab, d_model), 0.02),
            "pos": init(next(keys), (max_seq, d_model), 0.02),
            "ln_f_g": jnp.ones((d_model,), jnp.float32),
            "ln_f_b": jnp.zeros((d_model,), jnp.float32),
        }
        s = float(1.0 / np.sqrt(d_model))  # python float: stays float32
        # (gain, bias) rows, float32 like every other parameter: under the
        # package's x64 mode a dtype-less ones() is float64, which promotes
        # the whole residual stream (and hands the TPU kernel 64-bit
        # operands Mosaic refuses)
        ln = jnp.stack(
            [jnp.ones((d_model,), jnp.float32),
             jnp.zeros((d_model,), jnp.float32)]
        )
        for i in range(n_layers):
            p[f"l{i}_qkv"] = init(next(keys), (d_model, 3 * d_model), s)
            p[f"l{i}_proj"] = init(next(keys), (d_model, d_model), s)
            p[f"l{i}_mlp_up"] = init(next(keys), (d_model, 4 * d_model), s)
            p[f"l{i}_mlp_down"] = init(next(keys), (4 * d_model, d_model), s)
            p[f"l{i}_ln1"] = ln
            p[f"l{i}_ln2"] = ln
        self.params = p

    # ------------------------------------------------------------------
    def _attention(self, q, k, v, mesh: Optional[Mesh]):
        """(S, H, hd) -> (S, H, hd); ring attention per head when a mesh
        is given; on a TPU the Pallas kernel (forward compiled for the
        MXU, backward = `full_attention`'s VJP via the kernel's
        `custom_vjp`, so `train_step` differentiates it); full attention
        elsewhere."""
        qh = jnp.swapaxes(q, 0, 1)  # (H, S, hd)
        kh = jnp.swapaxes(k, 0, 1)
        vh = jnp.swapaxes(v, 0, 1)
        if mesh is not None:
            att = jax.vmap(
                lambda a, b, c: ring_attention(a, b, c, mesh, causal=True)
            )(qh, kh, vh)
        elif jax.default_backend() == "tpu":
            from ..ops.pallas_kernels import flash_attention

            att = jax.vmap(
                lambda a, b, c: flash_attention(a, b, c, causal=True)
            )(qh, kh, vh)
        else:
            att = jax.vmap(
                lambda a, b, c: full_attention(a, b, c, causal=True)
            )(qh, kh, vh)
        return jnp.swapaxes(att, 0, 1)

    def apply(self, params, tokens, mesh: Optional[Mesh] = None):
        """tokens: (S,) int32 -> logits (S, vocab)."""
        S = tokens.shape[0]
        h = params["embed"][tokens] + params["pos"][:S]
        for i in range(self.n_layers):
            g1, b1 = params[f"l{i}_ln1"]
            x = _layer_norm(h, g1, b1)
            qkv = x @ params[f"l{i}_qkv"]  # (S, 3*D)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            shape = (S, self.n_heads, self.head_dim)
            att = self._attention(
                q.reshape(shape), k.reshape(shape), v.reshape(shape), mesh
            )
            h = h + att.reshape(S, self.d_model) @ params[f"l{i}_proj"]
            g2, b2 = params[f"l{i}_ln2"]
            x = _layer_norm(h, g2, b2)
            h = h + jax.nn.gelu(x @ params[f"l{i}_mlp_up"]) @ params[f"l{i}_mlp_down"]
        h = _layer_norm(h, params["ln_f_g"], params["ln_f_b"])
        return h @ params["embed"].T

    def loss(self, params, tokens, mesh: Optional[Mesh] = None):
        """Next-token cross-entropy over a (S,) sequence."""
        logits = self.apply(params, tokens[:-1], mesh)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(
            jnp.take_along_axis(logp, tokens[1:, None], axis=1)
        )

    def train_step(self, params, tokens, lr=1e-2, mesh: Optional[Mesh] = None):
        loss, grads = jax.value_and_grad(self.loss)(params, tokens, mesh)
        new = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
        return new, loss

    # ------------------------------------------------------------------
    # Combined DP x SP x TP training step over a ("data","seq","model")
    # mesh: batch sharded over "data", sequence over "seq" (ring
    # attention), heads/FFN/vocab over "model" (Megatron-style column/row
    # splits with psum combines). The reference has no parallelism beyond
    # Spark data partitioning (SURVEY.md §2.5); this is the framework's
    # all-axes-at-once witness.
    # ------------------------------------------------------------------
    def _layout_table(self):
        """Single schema all three layout views derive from: rows are
        (flat param name, "rep"|"shd", layout key, to-layout shape or
        None, from-layout shape or None, PartitionSpec)."""
        from jax.sharding import PartitionSpec as P

        D, H, hd = self.d_model, self.n_heads, self.head_dim
        rows = [
            ("embed", "rep", "embed", None, None, P()),
            ("pos", "rep", "pos", None, None, P()),
            ("ln_f_g", "rep", "ln_f_g", None, None, P()),
            ("ln_f_b", "rep", "ln_f_b", None, None, P()),
        ]
        for i in range(self.n_layers):
            rows += [
                (f"l{i}_ln1", "rep", f"l{i}_ln1", None, None, P()),
                (f"l{i}_ln2", "rep", f"l{i}_ln2", None, None, P()),
                (f"l{i}_qkv", "shd", f"l{i}_qkv",
                 (D, 3, H, hd), (D, 3 * D), P(None, None, "model", None)),
                (f"l{i}_proj", "shd", f"l{i}_proj",
                 (H, hd, D), (D, D), P("model", None, None)),
                (f"l{i}_mlp_up", "shd", f"l{i}_up",
                 None, None, P(None, "model")),
                (f"l{i}_mlp_down", "shd", f"l{i}_down",
                 None, None, P("model", None)),
            ]
        return rows

    def device_layout(self, params) -> Dict[str, Dict[str, jax.Array]]:
        """Re-layout ``params`` for the 3D-sharded step: ``rep`` holds
        logically replicated tensors, ``shd`` holds model-axis-sharded
        ones (qkv/proj reshaped so the head axis is shardable)."""
        out = {"rep": {}, "shd": {}}
        for flat, kind, key, to_shape, _, _ in self._layout_table():
            v = params[flat]
            out[kind][key] = v if to_shape is None else jnp.reshape(v, to_shape)
        return out

    def merge_layout(self, layout) -> Dict[str, jax.Array]:
        """Inverse of `device_layout` (gathers back the flat param dict)."""
        p = {}
        for flat, kind, key, _, from_shape, _ in self._layout_table():
            v = layout[kind][key]
            p[flat] = v if from_shape is None else jnp.reshape(v, from_shape)
        return p

    def _layout_specs(self):
        out = {"rep": {}, "shd": {}}
        for _, kind, key, _, _, spec in self._layout_table():
            out[kind][key] = spec
        return out

    def sharded_train_step_3d(self, mesh: Mesh, lr: float = 1e-2):
        """One jitted SGD step over a ("data","seq","model") mesh.

        tokens: (batch, seq) int32, batch % data == 0, seq % seq_axis == 0;
        all `seq` positions are consumed (position t predicts t+1; the
        final global position is loss-masked). Gradient correctness under
        manual sharding: backprop is linear in cotangents, so per-shard
        partial grads sum to the true grad — replicated params psum over
        all three axes, model-sharded params over ("data","seq") only.
        The vocab axis of the tied output projection is sharded over
        "model" so no loss-path work is duplicated across TP shards.
        """
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        D, H, hd, V = self.d_model, self.n_heads, self.head_dim, self.vocab
        n_seq = mesh.shape["seq"]
        mp = mesh.shape["model"]
        if H % mp or V % mp:
            raise ValueError(
                f"n_heads={H} and vocab={V} must divide model axis {mp}"
            )
        v_per = V // mp
        scale = float(1.0 / np.sqrt(hd))
        ring = functools.partial(
            _ring_shard, axis_name="seq", causal=True, scale=scale
        )

        def local_loss(lp, toks):
            rep, shd = lp["rep"], lp["shd"]
            B, S = toks.shape  # local shard sizes
            if S * n_seq > rep["pos"].shape[0]:
                raise ValueError(
                    f"sequence length {S * n_seq} exceeds max_seq "
                    f"{rep['pos'].shape[0]} (dynamic_slice would silently "
                    "clamp and reuse positions)"
                )
            sidx = lax.axis_index("seq")
            midx = lax.axis_index("model")
            pos0 = sidx * S
            zero = jnp.zeros((), pos0.dtype)
            h = rep["embed"][toks] + lax.dynamic_slice(
                rep["pos"], (pos0, zero), (S, D)
            )[None]
            for i in range(self.n_layers):
                g1, b1 = rep[f"l{i}_ln1"]
                x = _layer_norm(h, g1, b1)
                qkv = jnp.einsum("bsd,dchk->cbhsk", x, shd[f"l{i}_qkv"])
                att = jax.vmap(jax.vmap(ring))(qkv[0], qkv[1], qkv[2])
                h = h + lax.psum(
                    jnp.einsum("bhsk,hkd->bsd", att, shd[f"l{i}_proj"]),
                    "model",
                )
                g2, b2 = rep[f"l{i}_ln2"]
                x = _layer_norm(h, g2, b2)
                u = jax.nn.gelu(x @ shd[f"l{i}_up"])
                h = h + lax.psum(u @ shd[f"l{i}_down"], "model")
            hf = _layer_norm(h, rep["ln_f_g"], rep["ln_f_b"])
            logits = hf @ lax.dynamic_slice(
                rep["embed"], (midx * v_per, zero), (v_per, D)
            ).T  # (B, S, V/mp)
            # next-token targets: shift left, final column comes from the
            # right ring neighbor (the global last position is masked out)
            nxt = lax.ppermute(
                toks[:, :1], "seq",
                [((j + 1) % n_seq, j) for j in range(n_seq)],
            )
            tgt = jnp.concatenate([toks[:, 1:], nxt], axis=1)
            gpos = pos0 + jnp.arange(S)
            w = (gpos < S * n_seq - 1).astype(jnp.float32)
            # cross-entropy over the vocab-sharded logits
            m = lax.pmax(
                lax.stop_gradient(jnp.max(logits, -1)), "model"
            )
            se = lax.psum(
                jnp.sum(jnp.exp(logits - m[..., None]), -1), "model"
            )
            idx = tgt - midx * v_per
            in_rng = (idx >= 0) & (idx < v_per)
            safe = jnp.clip(idx, 0, v_per - 1)
            val = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
            tgt_logit = lax.psum(jnp.where(in_rng, val, 0.0), "model")
            ll = tgt_logit - m - jnp.log(se)  # (B, S)
            num = lax.psum(jnp.sum(ll * w[None]), ("data", "seq"))
            # the count only varies over "seq" (it comes from axis_index
            # alone); cast it varying over "data" so one psum counts every
            # (batch, position) pair
            den = lax.psum(
                lax.pcast(
                    jnp.sum(jnp.broadcast_to(w[None], ll.shape)),
                    "data", to="varying",
                ),
                ("data", "seq"),
            )
            return -num / den

        def step(lp, toks):
            # with VMA tracking on (check_vma=True), shard_map autodiff
            # accounts for replication: grads of replicated params arrive
            # already summed over all mesh axes, grads of model-sharded
            # params arrive per-shard — no manual grad psums.
            loss, g = jax.value_and_grad(local_loss)(lp, toks)
            new = jax.tree_util.tree_map(lambda p, gg: p - lr * gg, lp, g)
            return new, loss

        specs = self._layout_specs()
        return jax.jit(
            shard_map(
                step,
                mesh=mesh,
                in_specs=(specs, P("data", "seq")),
                out_specs=(specs, P()),
            )
        )
