"""A language model built from a configuration, for scoring a token column.

The configuration is a dict with a published ``config.json``'s keys
(``hidden_size``, ``layer_types``, ``num_dense_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``moe_intermediate_size``, ``num_experts``, ``num_experts_per_tok``,
``conv_L_cache``, ``rope_theta``, ``norm_eps``, ``vocab_size``, the
router's options), under either of two families' names for them
(`family_keys`: ``first_k_dense_replace``, ``n_routed_experts``,
``rms_norm_eps``, ``scoring_func``, ``topk_method``): there is no class
per model. A layer is ``r = h + Op(RMSNorm(h))``,
``h' = r + FFN(RMSNorm(r))`` with ``Op`` by ``layer_types[i]`` — "conv",
the gated short convolution; "full_attention", grouped-query attention
with RoPE and per-head q/k norms; or "latent_attention" (every layer of a
configuration that has ``kv_lora_rank`` and no ``layer_types``): queries
through a normed ``q_lora_rank`` latent, keys and values through a normed
``kv_lora_rank`` latent, a score of a per-head part (``qk_nope_head_dim``)
and a rotary part (``qk_rope_head_dim``) whose key is one vector a token
for all heads, values of ``v_head_dim``. Both attentions run on
`ops.pallas_kernels.flash_attention`. ``FFN`` is a dense SwiGLU in the
first ``num_dense_layers`` layers, the expert layer of `models.moe` in the
others, with ``n_shared_experts`` shared experts (one SwiGLU of their
summed width) added for every token where the configuration has them.

Parameters are one pytree of arrays stacked by kind (every conv part's
``w_in`` in one array, every expert layer's ``w_up`` in one, ...), and all
layers run under ONE `lax.scan` whose step picks the layer's operator and
its FFN with `lax.switch`: compile time does not grow with depth, and each
kernel is in the program, and in a device trace, once. Weights are
bfloat16 (``config["dtype"]``), made on the device from a seed; the
residual stream, norms, softmax, router scores and the log-sum-exp are
float32; matmuls take bfloat16 operands and accumulate in float32.

`scoring_fn(config)` is the plain function a verb runs over a block:
``tfs.map_blocks(fn, frame, bindings={"params": params})``. Its outputs
have a row for every input row: ``token_logprob`` (rows, seq) float32 —
position t holds log p(token t+1 | tokens <= t), the last position 0 —
``expert_load`` (rows, expert layers, num_experts) int32, how many of
the row's tokens each expert got, and ``expert_choice`` (rows, expert
layers, seq, experts per token) int32, the experts each token went to (so
that a checker can follow the very routing the program took: a rounded
residual stream swaps a token's k-th and (k+1)-th expert where their
scores are close, and every later number then differs for that reason
alone). `score` is that call with its counters.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.pallas_kernels import flash_attention
from . import moe

__all__ = [
    "init_params", "scoring_fn", "score", "layer_plan", "held_all", "family_keys",
]

OPS = ("conv", "full_attention", "latent_attention")
ATTENTION = (1, 2)  # the operator kinds that attend
HEAD_CHUNK = 2048  # tokens whose logits exist at one time


def family_keys(config) -> dict:
    """``config`` with this module's names for what either family's
    ``config.json`` says: ``first_k_dense_replace`` is ``num_dense_layers``,
    ``n_routed_experts`` ``num_experts``, ``rms_norm_eps`` ``norm_eps``,
    ``scoring_func`` ``router_score``, ``topk_method: noaux_tc`` a
    per-expert bias in the choice; without ``layer_types`` every one of
    ``num_hidden_layers`` is latent attention. What this module does not
    compute raises, by its key: a group-limited expert choice, RoPE
    length scaling."""
    c = dict(config)
    if "layer_types" not in c:
        if "kv_lora_rank" not in c:
            raise ValueError("a configuration gives layer_types or kv_lora_rank")
        c["layer_types"] = ["latent_attention"] * int(c["num_hidden_layers"])
    for ours, theirs in (("num_dense_layers", "first_k_dense_replace"),
                         ("num_experts", "n_routed_experts"),
                         ("norm_eps", "rms_norm_eps"),
                         ("router_score", "scoring_func")):
        if ours not in c and theirs in c:
            c[ours] = c[theirs]
    if "use_expert_bias" not in c and "topk_method" in c:
        c["use_expert_bias"] = c["topk_method"] == "noaux_tc"
    for key in ("n_group", "topk_group"):
        if int(c.get(key) or 1) != 1:
            raise ValueError(
                f"{key} = {c[key]}: a group-limited expert choice is not computed here"
            )
    if c.get("rope_scaling") is not None:
        raise ValueError(
            f"rope_scaling = {c['rope_scaling']!r}: RoPE length scaling is not computed here"
        )
    return c


def held_all(config) -> Tuple[int, int]:
    return (0, int(family_keys(config)["num_experts"]))


def layer_plan(config):
    """Per layer: (operator kind, index in that kind's stack, 1 if the FFN
    is the expert layer, index in that FFN kind's stack), as int32 rows."""
    config = family_keys(config)
    types = list(config["layer_types"])
    dense = int(config["num_dense_layers"])
    seen = {k: 0 for k in OPS}
    rows = []
    for i, t in enumerate(types):
        is_moe = int(i >= dense)
        rows.append((OPS.index(t), seen[t], is_moe, i - dense if is_moe else i))
        seen[t] += 1
    return np.asarray(rows, dtype=np.int32).reshape(len(types), 4)


def _head_dim(config) -> int:
    return int(config.get("head_dim") or
               config["hidden_size"] // config["num_attention_heads"])


def _latent_shapes(config) -> Dict[str, Tuple[int, ...]]:
    """One latent-attention layer's arrays: ``w_qb`` holds a head's
    ``[q_n | q_r]`` side by side, ``w_kva`` ``[c_kv | k_r]``, ``w_kvb`` a
    head's ``[k_n | v]``, as the family's checkpoints do."""
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    if not config.get("q_lora_rank"):
        raise ValueError("q_lora_rank: queries without a latent are not computed here")
    rq, rkv = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv = int(config["v_head_dim"])
    return {
        "w_qa": (d, rq), "q_norm": (rq,), "w_qb": (rq, heads * (dn + dr)),
        "w_kva": (d, rkv + dr), "kv_norm": (rkv,),
        "w_kvb": (rkv, heads * (dn + dv)), "w_o": (heads * dv, d),
    }


def _shared_width(config) -> int:
    return int(config.get("n_shared_experts") or 0) * int(config["moe_intermediate_size"])


def init_params(config, seed: int, held: Optional[Tuple[int, int]] = None):
    """The model's parameters on the default device, from ``seed``:
    normal(0, ``initializer_range``) matrices, norm gains near 1, the
    convolution's taps normal(0, 1/sqrt(kernel)), the router bias
    normal(0, ``router_bias_range``), the experts' down projections
    normal(0, ``expert_out_range``) and the latent queries' up projection
    normal(0, ``query_out_range``) where the configuration gives one.
    ``held = (first, count)`` makes only those experts' weights (the
    router keeps its full width)."""
    config = family_keys(config)
    d, v = int(config["hidden_size"]), int(config["vocab_size"])
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    hd, k = _head_dim(config), int(config.get("conv_L_cache", 1))
    f, fe = int(config["intermediate_size"]), int(config["moe_intermediate_size"])
    e = int(config["num_experts"])
    _, count = held or held_all(config)
    plan = layer_plan(config)
    n_conv = int(np.sum(plan[:, 0] == 0))
    n_attn = int(np.sum(plan[:, 0] == 1))
    n_mla = int(np.sum(plan[:, 0] == 2))
    n_moe = int(np.sum(plan[:, 2]))
    n_dense = len(plan) - n_moe
    dtype = jnp.dtype(config.get("dtype", "bfloat16"))
    std = float(config.get("initializer_range", 0.02))
    bias_std = float(config.get("router_bias_range", 0.1))

    shapes = {
        "embed": ((v, d), std), "head": ((d, v), std),
        "final_norm": ((d,), None),
        "op_norm": ((len(plan), d), None), "ffn_norm": ((len(plan), d), None),
        "conv": {"w_in": ((n_conv, d, 3 * d), std),
                 "taps": ((n_conv, k, d), float(1.0 / np.sqrt(k))),
                 "w_out": ((n_conv, d, d), std)},
        "attn": {"w_qkv": ((n_attn, d, (heads + 2 * kv) * hd), std),
                 "q_norm": ((n_attn, hd), None), "k_norm": ((n_attn, hd), None),
                 "w_o": ((n_attn, heads * hd, d), std)},
        "dense": {"w_up": ((n_dense, d, 2 * f), std),
                  "w_down": ((n_dense, f, d), std)},
        "moe": {"router": ((n_moe, d, e), std),
                "bias": ((n_moe, e), bias_std),
                "w_up": ((n_moe, count, d, 2 * fe), std),
                "w_down": ((n_moe, count, fe, d),
                           float(config.get("expert_out_range", std)))},
    }
    if n_mla:
        scale = {"q_norm": None, "kv_norm": None,
                 "w_qb": float(config.get("query_out_range", std))}
        shapes["mla"] = {
            name: ((n_mla,) + shape, scale.get(name, std))
            for name, shape in _latent_shapes(config).items()
        }
        for kind, n in (("conv", n_conv), ("attn", n_attn)):
            if not n:  # this family has the stacks of the operators it has
                del shapes[kind]
    fs = _shared_width(config)
    if fs:
        shapes["moe"]["shared_up"] = ((n_moe, d, 2 * fs), std)
        shapes["moe"]["shared_down"] = ((n_moe, fs, d), std)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )

    def make(key, shape, scale):
        if scale is None:  # a norm's gain
            x = 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
        else:
            x = jnp.float32(scale) * jax.random.normal(key, shape, jnp.float32)
        return x.astype(dtype)

    keys = jax.random.split(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), len(leaves))
    made = [
        jax.jit(make, static_argnums=(1, 2))(k_, shape, scale)
        for k_, (shape, scale) in zip(keys, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, made)


def _rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(
        jnp.float32
    )


def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: lax.dynamic_index_in_dim(a, i, 0, False), tree)


def _matmul(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _conv_op(config, p, u):
    """Gated short convolution: [B, C, X] = u W_in; z = B*X; depthwise
    causal taps over z; y = (C * c) W_out."""
    with jax.named_scope("lm.conv"):
        b, c, x = jnp.split(_matmul(u, p["w_in"]), 3, axis=-1)
        z = b * x
        k = p["taps"].shape[0]
        zp = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))  # zero to the left
        seq = z.shape[1]
        conv = sum(
            p["taps"][j].astype(jnp.float32) * zp[:, k - 1 - j:k - 1 - j + seq]
            for j in range(k)
        )
        return _matmul(c * conv, p["w_out"])


def _rope(x, theta, interleave: bool = False):
    """RoPE over (rows, heads, seq, hd), float32: rotate-half (pairs
    ``(i, i + hd/2)``), or with ``interleave`` pairs ``(2i, 2i + 1)``."""
    hd, seq = x.shape[-1], x.shape[-2]
    inv = jnp.float32(theta) ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    if interleave:
        pairs = x.reshape(x.shape[:-1] + (hd // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        return jnp.stack(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
        ).reshape(x.shape)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention_op(config, p, u, interpret):
    with jax.named_scope("lm.attention"):
        rows, seq, _ = u.shape
        heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
        hd, eps = _head_dim(config), float(config["norm_eps"])
        qkv = _matmul(u, p["w_qkv"]).reshape(rows, seq, heads + 2 * kv, hd)
        qkv = jnp.swapaxes(qkv, 1, 2)  # (rows, heads + 2 kv, seq, hd)
        q, k, v = qkv[:, :heads], qkv[:, heads:heads + kv], qkv[:, heads + kv:]
        theta = float(config["rope_theta"])
        q = _rope(_rms_norm(q, p["q_norm"], eps), theta)
        k = _rope(_rms_norm(k, p["k_norm"], eps), theta)
        dtype = p["w_qkv"].dtype
        block = min(512, max(8, seq))
        att = flash_attention(
            q.astype(dtype), k.astype(dtype), v.astype(dtype), causal=True,
            scale=float(1.0 / np.sqrt(hd)), block_q=block, block_k=block,
            interpret=interpret,
        )
        att = jnp.swapaxes(att, 1, 2).reshape(rows, seq, heads * hd)
        return _matmul(att, p["w_o"])


def _latent_attention_op(config, p, u, interpret):
    """Latent attention: the score's per-head part ``q_n k_n^T`` and its
    rotary part ``q_r k_r^T`` (``k_r`` one vector a token for all heads)
    are the kernel's two score parts; nothing is concatenated or repeated."""
    with jax.named_scope("lm.mla"):
        rows, seq, _ = u.shape
        heads, eps = int(config["num_attention_heads"]), float(config["norm_eps"])
        rkv = int(config["kv_lora_rank"])
        dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
        theta = float(config["rope_theta"])
        pairs = bool(config.get("rope_interleave", False))
        dtype = p["w_qa"].dtype

        def by_head(x):  # (rows, seq, heads * w) -> (rows, heads, seq, w)
            return jnp.swapaxes(x.reshape(rows, seq, heads, -1), 1, 2)

        with jax.named_scope("mla.project"):
            # a head's [q_n | q_r] columns apart, so that q_n and [k_n | v]
            # leave their matmuls rounded as the kernel takes them and only
            # the rotary parts pass through float32 (the same products)
            w_qb = p["w_qb"].reshape(-1, heads, dn + dr)
            c_q = _rms_norm(_matmul(u, p["w_qa"]), p["q_norm"], eps)
            q_n = by_head(_matmul(c_q, w_qb[..., :dn].reshape(-1, heads * dn)).astype(dtype))
            q_r = by_head(_matmul(c_q, w_qb[..., dn:].reshape(-1, heads * dr)))
            kva = _matmul(u, p["w_kva"])
            c_kv = _rms_norm(kva[..., :rkv], p["kv_norm"], eps)
            kv = by_head(_matmul(c_kv, p["w_kvb"]).astype(dtype))
            q_r = _rope(q_r, theta, pairs)
            k_r = _rope(kva[:, None, :, rkv:], theta, pairs)  # one head
        # 1,024-blocks: at 32,768 positions the kernel's grid steps, not
        # its matmuls, bound 512-blocks (my chip run, PR 33: 151 -> 110 ms)
        block = min(1024, max(8, seq))
        att = flash_attention(
            q_n, kv[..., :dn], kv[..., dn:], q2=q_r.astype(dtype), k2=k_r.astype(dtype),
            causal=True, scale=float(1.0 / np.sqrt(dn + dr)),
            block_q=block, block_k=block, interpret=interpret,
        )
        with jax.named_scope("mla.project"):
            att = jnp.swapaxes(att, 1, 2).reshape(rows, seq, -1)
            return _matmul(att, p["w_o"])


def _dense_ffn(p, u):
    """SwiGLU over the tokens ``u`` (..., d), in as many parts as keep the
    up projection's float32 output and the activation within
    `moe.PART_BYTES` (`moe.parts_for`, one expert a token)."""
    d, up = p["w_up"].shape
    f = up // 2

    def part(x):
        h = _matmul(x, p["w_up"])
        return _matmul(jax.nn.silu(h[..., :f]) * h[..., f:], p["w_down"])

    flat = u.reshape(-1, d)
    n = moe.parts_for(flat.shape[0], 1, d, up, f, p["w_up"].dtype.itemsize)
    if n == 1:
        return part(u)
    return lax.map(part, flat.reshape(n, -1, d)).reshape(u.shape)


def _moe_ffn(config, stacks, i, u, held):
    """The expert layer ``i`` of the stacks ``stacks``: the experts'
    weights stay in their stacks (`moe.held_experts` with ``layer``)."""
    big = ("w_up", "w_down")
    p = _at({k: v for k, v in stacks.items() if k not in big}, i)
    rows, seq, d = u.shape
    e, top_k = int(config["num_experts"]), int(config["num_experts_per_tok"])
    flat = u.reshape(rows * seq, d)
    idx, w = moe.route(
        flat, p["router"], p["bias"] if config.get("use_expert_bias") else None,
        top_k=top_k, score=config.get("router_score", "sigmoid"),
        norm_topk=bool(config.get("norm_topk_prob", True)),
        scale=float(config.get("routed_scaling_factor", 1.0)),
    )
    y = moe.held_experts(
        flat.astype(stacks["w_up"].dtype), idx, w, stacks["w_up"], stacks["w_down"],
        held, layer=i,
    )
    if "shared_up" in p:  # once for every token, whatever is held here
        with jax.named_scope("moe.shared"):
            y = y + _dense_ffn({"w_up": p["shared_up"], "w_down": p["shared_down"]}, flat)
    load = jnp.sum(
        idx.reshape(rows, seq * top_k, 1) == jnp.arange(e, dtype=jnp.int32),
        axis=1, dtype=jnp.int32,
    )
    return y.reshape(rows, seq, d), load, idx.reshape(rows, seq, top_k)


def _head(config, params, h, tokens):
    """log p(next token) per position, the logits made a chunk of tokens
    at a time: (rows, seq) float32, the last position 0."""
    with jax.named_scope("lm.head"):
        rows, seq, d = h.shape
        x = _rms_norm(h, params["final_norm"], float(config["norm_eps"]))
        target = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        n = rows * seq
        chunk = min(HEAD_CHUNK, n)
        pad = (-n) % chunk
        x = jnp.pad(x.reshape(n, d), ((0, pad), (0, 0)))
        t = jnp.pad(target.reshape(n), (0, pad))

        def one(args):
            xc, tc = args
            logits = _matmul(xc, params["head"])
            lse = jax.nn.logsumexp(logits, axis=-1)
            return moe._along_rows(logits, tc[:, None])[:, 0] - lse

        lp = lax.map(one, (x.reshape(-1, chunk, d), t.reshape(-1, chunk)))
        lp = lp.reshape(-1)[:n].reshape(rows, seq)
        return lp.at[:, -1].set(0.0)


def _pick(branches: Dict, present, which, *args):
    """`lax.switch` over the kinds a model has; one kind needs none."""
    if len(present) == 1:
        return branches[present[0]](*args)
    return lax.switch(which, [branches[k] for k in present], *args)


def scoring_fn(
    config, held: Optional[Tuple[int, int]] = None, interpret: bool = False,
) -> Callable:
    """``fn(tokens, params) -> {"token_logprob", "expert_load",
    "expert_choice"}`` over a
    block of ``(rows, seq)`` token ids (the verb feeds the column named
    as the parameter, ``tokens``). The attention kernel compiles for the
    TPU; ``interpret=True`` (a CPU test, an example) interprets it, and
    nothing chooses that from the backend: a run on the chip is never an
    interpreted one without saying so."""
    config = family_keys(config)
    held = tuple(held or held_all(config))
    plan = layer_plan(config)
    eps = float(config["norm_eps"])
    e, top_k = int(config["num_experts"]), int(config["num_experts_per_tok"])
    ops_present = sorted(set(plan[:, 0].tolist()))
    ffn_present = sorted(set(plan[:, 2].tolist()))
    moe_layers = np.flatnonzero(plan[:, 2]).astype(np.int32)

    def lm_score(tokens, params):
        tokens = tokens.astype(jnp.int32)
        rows, seq = tokens.shape
        with jax.named_scope("lm.embed"):
            h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

        ops = {
            0: lambda u, i: _conv_op(config, _at(params["conv"], i), u),
            1: lambda u, i: _attention_op(config, _at(params["attn"], i), u, bool(interpret)),
            2: lambda u, i: _latent_attention_op(
                config, _at(params["mla"], i), u, bool(interpret)),
        }
        ffns = {
            0: lambda u, i: (_dense_ffn(_at(params["dense"], i), u),
                             jnp.zeros((rows, e), jnp.int32),
                             jnp.zeros((rows, seq, top_k), jnp.int32)),
            1: lambda u, i: _moe_ffn(config, params["moe"], i, u, held),
        }

        def layer(h, xs):
            row, op_gain, ffn_gain = xs
            r = h + _pick(ops, ops_present, row[0], _rms_norm(h, op_gain, eps), row[1])
            y, load, choice = _pick(
                ffns, ffn_present, row[2], _rms_norm(r, ffn_gain, eps), row[3]
            )
            return r + y, (load, choice)

        h, (loads, choices) = lax.scan(
            layer, h, (jnp.asarray(plan), params["op_norm"], params["ffn_norm"])
        )
        return {
            "token_logprob": _head(config, params, h, tokens),
            "expert_load": jnp.swapaxes(loads[moe_layers], 0, 1),
            "expert_choice": jnp.swapaxes(choices[moe_layers], 0, 1),
        }

    return lm_score


def score(fn: Callable, frame, params, config, **verb_args):
    """``tfs.map_blocks(fn, frame, bindings={"params": params})`` with the
    model's counters: ``lm.tokens`` (rows x seq of the frame),
    ``moe.routed_rows`` (tokens x experts per token x expert layers) and
    ``lm.attention_pairs`` (causal query-key pairs x heads x attention
    layers), all known on the host before the dispatch."""
    from .. import api
    from ..utils import telemetry

    seq = int(frame.column("tokens").values.shape[1])
    tokens = frame.nrows * seq
    plan = layer_plan(config)
    telemetry.counter_inc("lm.tokens", float(tokens))
    telemetry.counter_inc(
        "moe.routed_rows",
        float(tokens * int(config["num_experts_per_tok"]) * int(np.sum(plan[:, 2]))),
    )
    telemetry.counter_inc(
        "lm.attention_pairs",
        float(frame.nrows * (seq * (seq + 1) // 2)
              * int(config["num_attention_heads"])
              * int(np.isin(plan[:, 0], ATTENTION).sum())),
    )
    return api.map_blocks(fn, frame, bindings={"params": params}, **verb_args)
