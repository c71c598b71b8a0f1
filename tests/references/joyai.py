"""Plain reference of `joyai-llm-flash`: the forward pass in straightforward
`jax.numpy`, float32, every matmul at precision "highest"; nothing of the
package, no kernel, no sort, no grouped matmul, no scan over stacked
weights, no cache. `tests/references/joyai.py` is this file, letter for
letter (a tier-1 test compares the two).

The model (jdopensource/JoyAI-LLM-Flash config.json, read under its own
key names; d = hidden_size, ``u`` the normed input of a part):

- layer i: ``r = h + MLA(RMSNorm(h))``, ``h' = r + FFN(RMSNorm(r))``; every
  layer has the same attention.
- MLA (latent attention), ``num_attention_heads`` heads:
  ``c_q = RMSNorm(u W_qa)`` (q_lora_rank); ``[q_n | q_r] = c_q W_qb`` per
  head (qk_nope_head_dim | qk_rope_head_dim);
  ``[c_kv | k_r] = u W_kva`` (kv_lora_rank | qk_rope_head_dim),
  ``c_kv = RMSNorm(c_kv)``; ``[k_n | v] = c_kv W_kvb`` per head
  (qk_nope_head_dim | v_head_dim). ``k_r`` is ONE vector a token, shared by
  all heads. RoPE (rope_theta; ``rope_interleave``: pairs ``(2i, 2i+1)``) on
  ``q_r`` of every head and on ``k_r``; ``rope_scaling`` is null: no length
  scaling, no change of the softmax scale. The key of a head is written
  out, ``k = [k_n | k_r]``, and so is its query; scores
  ``q k^T / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal softmax,
  ``o = p v``, ``y = o W_o``. A head and a block of queries at a time, so
  that 32,768 positions fit.
- FFN of the first ``first_k_dense_replace`` layers: SwiGLU,
  ``(silu(u W1) * u W3) W2``. Of the others: ``s = sigmoid(u W_r)`` over
  all ``n_routed_experts`` in float32; the ``num_experts_per_tok`` chosen
  are the top-k of ``s + b`` (``topk_method: noaux_tc``: ``b`` is the
  per-expert correction bias, used for the choice only; ``n_group`` =
  ``topk_group`` = 1, so the group-limited choice is the plain one);
  weights are ``s`` at the chosen, over their sum (``norm_topk_prob``),
  times ``routed_scaling_factor``; the output is
  ``sum_k w_k SwiGLU_{e_k}(u) + SwiGLU_shared(u)``, the shared expert
  ``n_shared_experts * moe_intermediate_size`` wide. EVERY held expert is
  evaluated on every token in a plain loop and masked by its weight, 0
  where the token was not routed to it. ``held = (first, count)``: only
  those experts' weights are there and only their part of the sum is
  computed; the shared expert is computed whatever is held (the share test
  counts it once).
- ends: ``h0 = E[tokens]``; logits ``= RMSNorm(h_L) W_head`` (untied), a
  chunk of tokens at a time; ``token_logprob[t] =
  log_softmax(logits[t])[tokens[t+1]]``, the last 0.

Departures from the published description, each for a stated reason:
- the multi-token-prediction module (``num_nextn_predict_layers`` 1) is
  left out: log p(token t+1 | <= t) does not pass through it (it predicts
  token t+2: a training loss and a draft for speculative decoding).
- the RMSNorms on ``c_q`` and ``c_kv`` are assumed (the config names
  neither; the family's public code has both).

Weights, in this file's own layout (the equations' names; nothing is
stacked): ``{"embed" (vocabulary, d), "head" (d, vocabulary), "final_norm"
(d,), "layers": [...]}``, a layer being a mapping ``{"op_norm" (d,),
"ffn_norm" (d,), "op": ..., "ffn": ...}`` with ``op`` ``{"w_qa" (d, rq),
"q_a_norm" (rq,), "w_qb" (rq, heads*(dn+dr)), "w_kva" (d, rkv+dr),
"kv_a_norm" (rkv,), "w_kvb" (rkv, heads*(dn+dv)), "wo" (heads*dv, d)}``
(a head's columns side by side, as the family's checkpoints hold them) and
``ffn`` ``{"w1", "w3" (d, f), "w2" (f, d)}`` or ``{"router" (d, experts),
"bias" (experts,), "w1", "w3" (count, d, fe), "w2" (count, fe, d),
"shared_w1", "shared_w3" (d, fs), "shared_w2" (fs, d)}``. ``layers`` is
anything indexed by the layer's number; a layer's ``op`` and ``ffn`` are
asked for one after the other (a mapping that makes them when asked keeps
one part on the device at a time). All are read as float32, so the
reference and the program hold the same (bfloat16-rounded) numbers.

Three departures serve `correct` and its controls, and nothing else:
``operands="bfloat16"`` rounds each matmul's left operand to bfloat16 and,
in the attention, the queries, keys, values and the softmax's numerator:
the precision the configuration states (bfloat16 operands, float32
accumulation; the router's matmul stays in float32, as stated there);
``sum_chunk=n`` also keeps the running sums of the expert matmuls and of
the attention's two products in bfloat16, rounded after every ``n``
products: one step below it; and ``routing`` (rows, expert layers, seq,
top_k) names the experts each token goes to in the place of this file's
own top-k (the scores and weights stay its own): a rounded residual stream
swaps a token's k-th and (k+1)-th expert where their scores are close, one
swap moves every later number of the row, and so two computations agree to
their rounding only along one routing. `forward` also returns its own
top-k, so `compare` counts the tokens whose routing it would not have
chosen.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 2048  # queries whose scores exist at one time
HEAD_CHUNK = 2048   # tokens whose logits exist at one time


def _round(x, operands):
    return x.astype(jnp.bfloat16).astype(F32) if operands == "bfloat16" else x


def _mm(x, w, operands="float32"):
    return jnp.dot(_round(x.astype(F32), operands), w.astype(F32), precision="highest")


def _mm_bf16_sums(x, w, chunk):
    """x w with bfloat16 operands and a bfloat16 accumulator: the running
    sum is rounded to bfloat16 after every ``chunk`` products (inside a
    chunk they add up in float32, as one pass of a matrix unit does; a
    contraction that is no multiple of ``chunk`` ends in a shorter one). (A
    loop over the contraction: the control's own departure from "no loop
    primitive", like its precision.)"""
    k = x.shape[-1]
    chunk = min(int(chunk), k)
    pad = (-k) % chunk  # products with zero add nothing
    xb = jnp.pad(x.astype(jnp.bfloat16).astype(F32), ((0, 0), (0, pad)))
    w = jnp.pad(w.astype(F32), ((0, pad), (0, 0)))

    def add(i, acc):
        xs = jax.lax.dynamic_slice_in_dim(xb, i * chunk, chunk, axis=1)
        ws = jax.lax.dynamic_slice_in_dim(w, i * chunk, chunk, axis=0)
        return (acc.astype(F32) + jnp.dot(xs, ws, precision="highest")).astype(jnp.bfloat16)

    acc = jax.lax.fori_loop(
        0, (k + pad) // chunk, add, jnp.zeros((x.shape[0], w.shape[1]), jnp.bfloat16)
    )
    return acc.astype(F32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def rope(x, theta, interleave):
    """(..., seq, width): pairs ``(2i, 2i+1)`` with ``interleave``, else
    rotate-half pairs ``(i, i + width/2)``."""
    hd, seq = x.shape[-1], x.shape[-2]
    inv = F32(theta) ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(seq, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.zeros_like(x)
        out = out.at[..., 0::2].set(x1 * cos - x2 * sin)
        return out.at[..., 1::2].set(x1 * sin + x2 * cos)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def spec_of(config):
    """The configuration's numbers a layer needs, hashable, read under the
    published key names. What this file does not compute raises."""
    if int(config.get("n_group") or 1) != 1 or int(config.get("topk_group") or 1) != 1:
        raise ValueError("n_group / topk_group other than 1: not this file's mathematics")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling: not this file's mathematics")
    return (
        ("heads", int(config["num_attention_heads"])),
        ("rkv", int(config["kv_lora_rank"])),
        ("dn", int(config["qk_nope_head_dim"])), ("dr", int(config["qk_rope_head_dim"])),
        ("dv", int(config["v_head_dim"])),
        ("theta", float(config["rope_theta"])),
        ("interleave", bool(config.get("rope_interleave", False))),
        ("eps", float(config["rms_norm_eps"])),
        ("top_k", int(config["num_experts_per_tok"])),
        ("num_experts", int(config["n_routed_experts"])),
        ("use_bias", config.get("topk_method") == "noaux_tc"),
        ("norm_topk", bool(config.get("norm_topk_prob", True))),
        ("scale", float(config.get("routed_scaling_factor", 1.0))),
    )


@functools.partial(jax.jit, static_argnames=("spec", "operands"))
def mla_project(h, gain, p, *, spec, operands):
    """One row's queries, keys and values, (heads, seq, width) each but
    ``k_r`` (seq, dr): RoPE applied, rounded as the kernel's operands are."""
    c = dict(spec)
    heads, dn, rkv = c["heads"], c["dn"], c["rkv"]
    u = rms_norm(h, gain, c["eps"])  # (seq, d)

    def by_head(x):  # (seq, heads * w) -> (heads, seq, w)
        return jnp.swapaxes(x.reshape(x.shape[0], heads, -1), 0, 1)

    c_q = rms_norm(_mm(u, p["w_qa"], operands), p["q_a_norm"], c["eps"])
    q = by_head(_mm(c_q, p["w_qb"], operands))
    kva = _mm(u, p["w_kva"], operands)
    c_kv = rms_norm(kva[:, :rkv], p["kv_a_norm"], c["eps"])
    kv = by_head(_mm(c_kv, p["w_kvb"], operands))
    q_r = rope(q[..., dn:], c["theta"], c["interleave"])
    k_r = rope(kva[:, rkv:], c["theta"], c["interleave"])
    return tuple(_round(a, operands) for a in (
        q[..., :dn], q_r, kv[..., :dn], k_r, kv[..., dn:]))


@functools.partial(jax.jit, static_argnames=("operands", "sum_chunk"))
def attend(q_n, q_r, k_n, k_r, v, first, *, operands, sum_chunk):
    """One head's block of queries (positions ``first``...) against all
    the row's keys: plain softmax attention on the key written out."""
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate([k_n, k_r], axis=-1)  # the shared k_r, for this head too
    if sum_chunk:
        s = _mm_bf16_sums(q, k.T, sum_chunk)
    else:
        s = jnp.dot(q, k.T, precision="highest")
    pos = first + jnp.arange(q.shape[0])
    causal = pos[:, None] >= jnp.arange(k.shape[0])[None, :]
    s = jnp.where(causal, s / math.sqrt(q.shape[-1]), -jnp.inf)
    # softmax(s) v as (e v) / sum(e), e = exp(s - max s): e is the left
    # operand of a matmul, and is rounded as one
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    total = jnp.sum(e, axis=-1, keepdims=True)
    if sum_chunk:
        return _mm_bf16_sums(e, v, sum_chunk) / total
    return jnp.dot(_round(e, operands), v, precision="highest") / total


@functools.partial(jax.jit, static_argnames=("operands",))
def project_out(h, att, wo, *, operands):
    return h + _mm(att, wo, operands)


def mla(h, gain, p, *, spec, operands, sum_chunk):
    """``h + MLA(RMSNorm(h))`` of (rows, seq, d), a row, a head and a block
    of queries at a time."""
    heads = dict(spec)["heads"]
    out = []
    for r in range(h.shape[0]):
        q_n, q_r, k_n, k_r, v = mla_project(h[r], gain, p, spec=spec, operands=operands)
        seq = q_n.shape[1]
        per_head = []
        for a in range(heads):
            blocks = [
                attend(q_n[a, lo:lo + QUERY_BLOCK], q_r[a, lo:lo + QUERY_BLOCK],
                       k_n[a], k_r, v[a], lo, operands=operands, sum_chunk=sum_chunk)
                for lo in range(0, seq, QUERY_BLOCK)
            ]
            per_head.append(jnp.concatenate(blocks, axis=0))  # (seq, dv)
        att = jnp.concatenate(per_head, axis=-1)  # (seq, heads * dv)
        out.append(project_out(h[r], att, p["wo"], operands=operands))
    return jnp.stack(out)


def swiglu(u, w1, w3, w2, operands, mm_experts=None):
    mm = mm_experts or functools.partial(_mm, operands=operands)
    return mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2)


@functools.partial(jax.jit, static_argnames=("spec", "operands"))
def dense_ffn(r, gain, f, *, spec, operands):
    u = rms_norm(r, gain, dict(spec)["eps"])
    return r + swiglu(u, f["w1"], f["w3"], f["w2"], operands)


@functools.partial(jax.jit, static_argnames=("spec",))
def route(r, gain, router, bias, routing, *, spec):
    """(normed input (n, d), weight of every expert on every token (n, E),
    0 where not routed, load (rows, E), own top-k (rows, seq, top_k))."""
    c = dict(spec)
    rows, seq, d = r.shape
    x = rms_norm(r, gain, c["eps"]).reshape(rows * seq, d)
    s = jax.nn.sigmoid(_mm(x, router))  # float32 operands, as stated
    choose = s + bias.astype(F32) if c["use_bias"] else s
    _, own = jax.lax.top_k(choose, c["top_k"])
    idx = own if routing is None else routing.reshape(rows * seq, c["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, c["num_experts"], dtype=F32), axis=1)  # 0/1
    w = s * chosen
    if c["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    load = jnp.sum(chosen.reshape(rows, seq, -1), axis=1).astype(jnp.int32)
    return x, w * c["scale"], load, own.reshape(rows, seq, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("operands", "sum_chunk"))
def add_expert(out, x, w1, w3, w2, weight, *, operands, sum_chunk=0):
    """``out + weight * SwiGLU(x)``: one expert on every token, masked by
    its weight (0 on a token not routed to it)."""
    mm = functools.partial(_mm_bf16_sums, chunk=sum_chunk) if sum_chunk else None
    return out + weight[:, None] * swiglu(x, w1, w3, w2, operands, mm)


def moe_ffn(r, gain, f, routing, *, spec, held, operands, sum_chunk):
    """(``r + FFN(RMSNorm(r))``, load, own top-k): every held expert in a
    plain loop, then the shared expert."""
    first, count = held
    x, w, load, own = route(r, gain, f["router"], f["bias"], routing, spec=spec)
    out = jnp.zeros_like(x)
    for e in range(count):
        out = add_expert(out, x, f["w1"][e], f["w3"][e], f["w2"][e], w[:, first + e],
                         operands=operands, sum_chunk=sum_chunk)
    if "shared_w1" in f:  # every token, whatever is held
        out = add_expert(out, x, f["shared_w1"], f["shared_w3"], f["shared_w2"],
                         jnp.ones(x.shape[:1], F32), operands=operands)
    return r + out.reshape(r.shape), load, own


@functools.partial(jax.jit, static_argnames=("eps", "operands"))
def head_chunk(h, final_norm, w_head, target, *, eps, operands):
    logits = _mm(rms_norm(h, final_norm, eps), w_head, operands)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]


def head(h, final_norm, w_head, tokens, *, eps, operands):
    rows, seq, d = h.shape
    target = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1).reshape(-1)
    flat = h.reshape(rows * seq, d)
    lp = jnp.concatenate([
        head_chunk(flat[lo:lo + HEAD_CHUNK], final_norm, w_head,
                   target[lo:lo + HEAD_CHUNK], eps=eps, operands=operands)
        for lo in range(0, rows * seq, HEAD_CHUNK)
    ]).reshape(rows, seq)
    return lp.at[:, -1].set(0.0)


def forward(config, weights, tokens, held=None, operands="float32",
            sum_chunk=0, routing=None):
    """(token_logprob (rows, seq) float32, expert_load (rows, expert
    layers, n_routed_experts) int32, own top-k (rows, expert layers, seq,
    top_k) int32) of ``tokens`` (rows, seq), layer by layer and part by
    part: what is on the device at one time is one part's weights and one
    part's activations."""
    held = tuple(held or (0, int(config["n_routed_experts"])))
    spec = spec_of(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    dense = int(config["first_k_dense_replace"])
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens].astype(F32)
        loads, owns = [], []
        for i in range(int(config["num_hidden_layers"])):
            w = weights["layers"][i]
            r = mla(h, w["op_norm"], w["op"], spec=spec, operands=operands,
                    sum_chunk=sum_chunk)
            if i < dense:
                h = dense_ffn(r, w["ffn_norm"], w["ffn"], spec=spec, operands=operands)
                continue
            forced = None
            if routing is not None:
                forced = jnp.asarray(routing, jnp.int32)[:, i - dense]
            h, load, own = moe_ffn(r, w["ffn_norm"], w["ffn"], forced, spec=spec,
                                   held=held, operands=operands, sum_chunk=sum_chunk)
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
