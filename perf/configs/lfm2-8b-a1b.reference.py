"""Plain reference of `lfm2-8b-a1b`: the forward pass in straightforward
`jax.numpy`, float32, every matmul at precision "highest"; nothing of the
package, no kernel, no scan, no cache. `tests/references/lfm2.py` is this
file, letter for letter (a tier-1 test compares the two).

The model (LiquidAI/LFM2-8B-A1B config.json; d = hidden_size):

- layer i: ``r = h + Op(RMSNorm(h))``, ``h' = r + FFN(RMSNorm(r))``;
  ``Op`` by ``layer_types[i]``.
- "conv": ``[B, C, X] = split(u W_in, 3)``; ``z = B * X``;
  ``c_t = sum_{j<K} taps_j * z_{t-j}`` (depthwise, causal, zero to the
  left, K = conv_L_cache); ``y = (C * c) W_out``.
- "full_attention": q, k, v heads of ``head_dim``; RMSNorm over each q and
  k head; rotate-half RoPE (rope_theta) on q and k; causal
  softmax(q k^T / sqrt(head_dim)) v, each key/value head serving
  ``heads // kv_heads`` consecutive query heads; ``W_o``.
- FFN of the first ``num_dense_layers`` layers: SwiGLU,
  ``(silu(u W1) * u W3) W2``. Of the others: ``s = sigmoid(u W_r)`` over
  all experts; the top-k of ``s + bias`` are chosen; weights are ``s`` at
  the chosen, over their sum, times ``routed_scaling_factor``; the output
  is ``sum_k w_k SwiGLU_{e_k}(u)``. EVERY expert is evaluated on every
  token here and masked. ``held = (first, count)``: only those experts'
  weights are there, and only their part of the sum is computed (the
  chip's share of a layer; `parts add up` is a tier-1 test).
- ends: ``h0 = E[tokens]``; logits ``= RMSNorm(h_L) W_head`` (untied);
  ``token_logprob[t] = log_softmax(logits[t])[tokens[t+1]]``, the last 0.

Weights, in this file's own layout (the equations' names; nothing is
stacked or fused): ``{"embed" (vocabulary, d), "head" (d, vocabulary),
"final_norm" (d,), "layers": [...]}``, a layer being ``{"op_norm" (d,),
"ffn_norm" (d,), "op": ..., "ffn": ...}`` with ``op`` ``{"w_in" (d, 3d),
"taps" (K, d), "w_out" (d, d)}`` or ``{"wq" (d, heads*hd), "wk", "wv"
(d, kv_heads*hd), "q_norm", "k_norm" (hd,), "wo" (heads*hd, d)}`` and
``ffn`` ``{"w1", "w3" (d, f), "w2" (f, d)}`` or ``{"router" (d, experts),
"bias" (experts,), "w1", "w3" (count, d, f), "w2" (count, f, d)}``.
``layers`` is anything indexed by the layer's number (one that makes a
layer when asked for it keeps one layer on the device at a time). All
are read as float32, so the reference and the program hold the same
(bfloat16-rounded) numbers.

Three departures serve `correct` and its controls, and nothing else:
``operands="bfloat16"`` rounds each matmul's left operand to bfloat16, the
precision the configuration states (bfloat16 operands, float32
accumulation; the router's matmul stays in float32, as stated there);
``expert_sum_chunk=n`` also keeps each expert matmul's running sum in
bfloat16, rounded after every ``n`` products: one step below it; and
``routing`` (rows, expert layers, seq, top_k) names the experts each token
goes to in the place of this file's own top-k (the scores and weights stay
its own): a rounded residual stream swaps a token's k-th and (k+1)-th
expert where their scores are close, one swap moves every later number of
the row, and so two computations agree to their rounding only along one
routing. `forward` also returns its own top-k, so `compare` counts the
tokens whose routing it would not have chosen.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _mm(x, w, operands="float32"):
    if operands == "bfloat16":
        x = x.astype(jnp.bfloat16)
    return jnp.dot(x.astype(F32), w.astype(F32), precision="highest")


def _mm_bf16_sums(x, w, chunk):
    """x w with bfloat16 operands and a bfloat16 accumulator: the running
    sum is rounded to bfloat16 after every ``chunk`` products (inside a
    chunk they add up in float32, as one pass of a matrix unit does). (A
    loop over the contraction: the control's own departure from "no loop
    primitive", like its precision.)"""
    k = x.shape[-1]
    chunk = min(int(chunk), k)
    if k % chunk:
        raise ValueError(f"a contraction of {k} in chunks of {chunk}")
    xb = x.astype(jnp.bfloat16).astype(F32)
    w = w.astype(F32)

    def add(i, acc):
        xs = jax.lax.dynamic_slice_in_dim(xb, i * chunk, chunk, axis=1)
        ws = jax.lax.dynamic_slice_in_dim(w, i * chunk, chunk, axis=0)
        return (acc.astype(F32) + jnp.dot(xs, ws, precision="highest")).astype(jnp.bfloat16)

    acc = jax.lax.fori_loop(
        0, k // chunk, add, jnp.zeros((x.shape[0], w.shape[1]), jnp.bfloat16)
    )
    return acc.astype(F32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def rope(x, theta):
    """(..., seq, head_dim), rotate-half."""
    hd, seq = x.shape[-1], x.shape[-2]
    inv = F32(theta) ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(seq, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def conv_op(p, u, operands):
    b, c, x = jnp.split(_mm(u, p["w_in"], operands), 3, axis=-1)
    z = b * x
    taps = p["taps"].astype(F32)
    conv = jnp.zeros_like(z)
    for j in range(taps.shape[0]):  # c_t += taps_j * z_{t-j}
        shifted = jnp.pad(z, ((0, 0), (j, 0), (0, 0)))[:, : z.shape[1]]
        conv = conv + taps[j] * shifted
    return _mm(c * conv, p["w_out"], operands)


def attention_op(p, u, operands, *, heads, kv_heads, head_dim, theta, eps):
    rows, seq, _ = u.shape
    def heads_of(w, n):  # (rows, n, seq, head_dim)
        return jnp.swapaxes(_mm(u, w, operands).reshape(rows, seq, n, head_dim), 1, 2)

    q = rope(rms_norm(heads_of(p["wq"], heads), p["q_norm"], eps), theta)
    k = rope(rms_norm(heads_of(p["wk"], kv_heads), p["k_norm"], eps), theta)
    v = heads_of(p["wv"], kv_heads)
    if operands == "bfloat16":
        q, k, v = (a.astype(jnp.bfloat16).astype(F32) for a in (q, k, v))
    group = heads // kv_heads
    causal = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
    out = []
    for r in range(rows):  # one row and one key/value head at a time
        per_head = []
        for g in range(kv_heads):
            qg = q[r, g * group:(g + 1) * group]
            s = jnp.einsum("hqd,kd->hqk", qg, k[r, g], precision="highest")
            s = jnp.where(causal, s / math.sqrt(head_dim), -jnp.inf)
            # softmax(s) v as (e v) / sum(e), e = exp(s - max s): e is the
            # left operand of a matmul, and is rounded as one
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            total = jnp.sum(e, axis=-1, keepdims=True)
            if operands == "bfloat16":
                e = e.astype(jnp.bfloat16).astype(F32)
            per_head.append(
                jnp.einsum("hqk,kd->hqd", e, v[r, g], precision="highest") / total)
        out.append(jnp.concatenate(per_head, axis=0))  # (heads, seq, hd)
    att = jnp.swapaxes(jnp.stack(out), 1, 2).reshape(rows, seq, heads * head_dim)
    return _mm(att, p["wo"], operands)


def swiglu(u, w1, w3, w2, operands, mm_experts=None):
    mm = mm_experts or functools.partial(_mm, operands=operands)
    return mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2)


def moe_ffn(p, u, operands, expert_sum_chunk, *, held, top_k, num_experts,
            use_bias, norm_topk, scale, routing=None):
    """(output, load, own top-k): every held expert on every token, masked
    by ``routing`` (rows, seq, top_k) where given, else by the own top-k."""
    rows, seq, d = u.shape
    x = u.reshape(rows * seq, d)
    s = jax.nn.sigmoid(_mm(x, p["router"]))  # float32 operands, as stated
    choose = s + p["bias"].astype(F32) if use_bias else s
    _, own = jax.lax.top_k(choose, top_k)
    idx = own if routing is None else routing.reshape(rows * seq, top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, num_experts, dtype=F32), axis=1)  # (n, E) 0/1
    w = s * chosen
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * scale
    mm = functools.partial(_mm_bf16_sums, chunk=expert_sum_chunk) if expert_sum_chunk else None
    first, count = held
    out = jnp.zeros_like(x)
    for e in range(count):
        y = swiglu(x, p["w1"][e], p["w3"][e], p["w2"][e], operands, mm)
        out = out + w[:, first + e, None] * y
    load = jnp.sum(chosen.reshape(rows, seq, num_experts), axis=1).astype(jnp.int32)
    return out.reshape(rows, seq, d), load, own.reshape(rows, seq, top_k).astype(jnp.int32)


def spec_of(config):
    """The configuration's numbers a layer needs, hashable."""
    heads = int(config["num_attention_heads"])
    return (
        ("heads", heads), ("kv_heads", int(config["num_key_value_heads"])),
        ("head_dim", int(config.get("head_dim") or config["hidden_size"] // heads)),
        ("theta", float(config["rope_theta"])), ("eps", float(config["norm_eps"])),
        ("top_k", int(config["num_experts_per_tok"])),
        ("num_experts", int(config["num_experts"])),
        ("use_bias", bool(config.get("use_expert_bias", False))),
        ("norm_topk", bool(config.get("norm_topk_prob", True))),
        ("scale", float(config.get("routed_scaling_factor", 1.0))),
    )


@functools.partial(jax.jit, static_argnames=(
    "op", "is_moe", "spec", "held", "operands", "expert_sum_chunk"))
def layer(h, w, routing=None, *, op, is_moe, spec, held, operands="float32",
          expert_sum_chunk=0):
    """One layer on the residual stream ``h`` (rows, seq, d) float32 with
    that layer's own weights ``w``; returns (h', load, own top-k), the last
    two None in a dense layer."""
    c = dict(spec)
    u = rms_norm(h, w["op_norm"], c["eps"])
    if op == "conv":
        r = h + conv_op(w["op"], u, operands)
    else:
        r = h + attention_op(
            w["op"], u, operands, heads=c["heads"], kv_heads=c["kv_heads"],
            head_dim=c["head_dim"], theta=c["theta"], eps=c["eps"],
        )
    u = rms_norm(r, w["ffn_norm"], c["eps"])
    f = w["ffn"]
    if not is_moe:
        return r + swiglu(u, f["w1"], f["w3"], f["w2"], operands), None, None
    y, load, own = moe_ffn(
        f, u, operands, expert_sum_chunk, held=held, top_k=c["top_k"],
        num_experts=c["num_experts"], use_bias=c["use_bias"],
        norm_topk=c["norm_topk"], scale=c["scale"], routing=routing,
    )
    return r + y, load, own


@functools.partial(jax.jit, static_argnames=("eps", "operands"))
def head(h, final_norm, w_head, tokens, *, eps, operands="float32"):
    logits = _mm(rms_norm(h, final_norm, eps), w_head, operands)
    logp = jax.nn.log_softmax(logits, axis=-1)
    target = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    lp = jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
    return lp.at[:, -1].set(0.0)


def forward(config, weights, tokens, held=None, operands="float32",
            expert_sum_chunk=0, routing=None):
    """(token_logprob (rows, seq) float32, expert_load (rows, expert
    layers, num_experts) int32, own top-k (rows, expert layers, seq, top_k)
    int32) of ``tokens`` (rows, seq), layer by layer: what is on the device
    at one time is one layer's weights in float32 and one layer's
    activations."""
    held = tuple(held or (0, int(config["num_experts"])))
    spec = spec_of(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    dense = int(config["num_dense_layers"])
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens].astype(F32)
        loads, owns = [], []
        for i, op in enumerate(config["layer_types"]):
            forced = None
            if routing is not None and i >= dense:
                forced = jnp.asarray(routing, jnp.int32)[:, i - dense]
            h, load, own = layer(
                h, weights["layers"][i], forced, op=op, is_moe=i >= dense,
                spec=spec, held=held, operands=operands,
                expert_sum_chunk=expert_sum_chunk,
            )
            if load is not None:
                loads.append(load)
                owns.append(own)
        lp = head(h, weights["final_norm"], weights["head"], tokens,
                  eps=dict(spec)["eps"], operands=operands)
    return lp, jnp.stack(loads, axis=1), jnp.stack(owns, axis=1)


NUMBERS = ("logprob_p99_abs_err", "routing_swapped_share", "expert_load_l1_share")


def compare(got, want, top_k):
    """The three numbers `correct` is decided on, over the checked rows.
    ``got`` is what the program gave, (token_logprob, expert_load,
    expert_choice); ``want`` what `forward` gives for the same rows ALONG
    THE PROGRAM'S ROUTING (``routing=got[2]``). The 99th percentile of
    |log-probability error| over the scored positions (the last of a row
    scores nothing): rounding alone, since both sides took one routing.
    The share of (token, expert layer) pairs whose experts are not the
    reference's own top-k there. The L1 distance of ``expert_load`` from
    the counts of the routing, over tokens x top_k x expert layers. A
    wrong shape, a NaN, or a token without ``top_k`` distinct experts of
    the model reads as infinite."""
    (got_lp, got_ld, got_ch), (want_lp, want_ld, own) = (
        [np.asarray(a) for a in side] for side in (got, want)
    )
    bad = dict.fromkeys(NUMBERS, float("inf"))
    if (got_lp.shape != want_lp.shape or got_ld.shape != want_ld.shape
            or got_ch.shape != own.shape or got_ch.shape[-1] != top_k):
        return bad
    ch = np.sort(got_ch.astype(np.int64), axis=-1)
    if ch.min() < 0 or ch.max() >= got_ld.shape[-1] or (np.diff(ch, axis=-1) == 0).any():
        return bad
    err = np.abs(got_lp.astype(np.float64) - want_lp.astype(np.float64))
    if np.isnan(err).any() or np.any(got_lp[:, -1] != 0.0):
        return bad
    swapped = np.any(ch != np.sort(own.astype(np.int64), axis=-1), axis=-1)
    routed = want_lp.shape[0] * want_lp.shape[1] * top_k * want_ld.shape[1]
    return {
        "logprob_p99_abs_err": float(np.percentile(err[:, :-1], 99)),
        "routing_swapped_share": float(np.mean(swapped)),
        "expert_load_l1_share":
            float(np.sum(np.abs(got_ld.astype(np.int64) - want_ld))) / routed,
    }
