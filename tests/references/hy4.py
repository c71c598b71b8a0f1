"""Plain reference of `hy4-preview`: the forward pass in straightforward
`jax.numpy`, float32, every matmul at precision "highest"; nothing of the
package, no kernel, no grouped matmul, no scan over stacked weights, no
cache. `perf/configs/hy4-preview.reference.py` is this file, letter for
letter (a tier-1 test compares the two).

The model (tencent/Hy4-preview config.json, read under its own key names;
d = hidden_size, n = hc_mult, ``u`` the normed input of a part):

- the residual is n float32 streams a token, X (n, d); X_0[i] = E[token].
- hyper-connections (mHC) around each sublayer F, attention then FFN:
  ``x = vec(X) / rms(vec(X))`` over n d, no gain; ``a_pre =
  sigmoid(alpha_0 x phi_pre + b_pre)``; ``a_post = hc_magnitude *
  sigmoid(alpha_1 x phi_post + b_post)``; ``M = Sinkhorn(exp(alpha_2
  mat(x phi_res) + b_res))``, 20 row-then-column normalisations with
  ``hc_eps`` in each denominator; ``X <- M X + a_post (x) F(RMSNorm(sum_i
  a_pre[i] X[i]))``. After the last layer ``h = sum_i a_head[i] X[i]``,
  ``a_head = sigmoid(alpha x phi_head + b_head)``. The maps take float32
  operands whatever the precision stated.
- attention (every layer): gated latent attention on a sparse index with a
  sink. ``c_q = RMSNorm(u W_qa)``; ``[q_n | q_r] = c_q W_qb`` a head;
  ``[c_kv | k_r] = u W_kva``, ``c_kv = RMSNorm(c_kv)``; ``[k_n | v] = c_kv
  W_kvb`` a head; RoPE (``rope_parameters.rope_theta``, rotate-half pairs)
  on ``q_r`` and on ``k_r`` (one vector a token for all heads). On a
  ``full`` layer (``indexer_types``) the lightning indexer: ``q_I = c_q
  W_qI`` (index_n_heads of index_head_dim), ``k_I = LayerNorm(u W_kI)``
  (gain and bias), RoPE on the first qk_rope_head_dim of both, ``w = u W_w
  / sqrt(index_n_heads)``, ``I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] k_I[s]
  / sqrt(index_head_dim))`` for s <= t, taken a block of queries at a time
  (the whole T x T in blocks); ``S_t`` = the index_topk largest (every s
  <= t while t < index_topk), by `jnp.argsort`. A ``shared`` layer takes
  ``S_t`` of the last full layer. Scores ``z = [q_n | q_r] . [k_n | k_r] /
  sqrt(qk_nope_head_dim + qk_rope_head_dim)`` for s in S_t; ``p = exp(z) /
  (exp(sink_h) + sum_{S_t} exp(z))``; ``o = p v``; ``y = (o *
  sigmoid(u W_g)) W_o``. A head and a block of queries at a time.
- FFN of the leading ``dense`` layers (``mlp_layer_types``): a SwiGLU; of
  the others: ``s = sigmoid(u W_r)`` over all ``n_routed_experts`` in
  float32, the top ``num_experts_per_tok`` of ``s`` (no correction bias:
  the config has no topk_method), weights ``s`` at the chosen over their
  sum times ``routed_scaling_factor``; ``sum_k w_k SwiGLU_{e_k}(u) +
  SwiGLU_shared(u)``. Every SwiGLU is ``silu(min(u W1, L)) * clip(u W3,
  -L, L)``, L = swiglu_limit. EVERY held expert is evaluated on every
  token in a plain loop and masked by its weight. ``held = (first,
  count)``: only those experts' weights are there and only their part is
  computed; the shared expert is computed whatever is held.
- ends: ``logits = RMSNorm(h) W_head`` in float32 (``enable_lm_head_fp32``:
  no rounding at any precision), a chunk of tokens at a time;
  ``token_logprob[t] = log_softmax(logits[t])[tokens[t+1]]``, the last 0.

Departures from the published description: the multi-token-prediction
module is left out (it predicts token t+2); the indexer takes bfloat16 (or
float32) operands, not FP8 after a Hadamard rotation; what the
configuration file lists under ``assumed``.

Weights, in this file's own layout: ``{"embed" (V, d), "head" (d, V),
"final_norm" (d,), "hc_head_phi" (n d, n), "hc_head_alpha" (1,),
"hc_head_bias" (n,), "layers": [...]}``, a layer being ``{"op_norm",
"ffn_norm" (d,), "op": {...}, "ffn": {...}, "hc_op": {...}, "hc_ffn":
{...}}``: ``op`` ``{"w_qa", "q_a_norm", "w_qb", "w_kva", "kv_a_norm",
"w_kvb", "wo", "w_g" (d, heads*dv), "sink" (heads,)}`` and on a full layer
``{"w_qI" (rq, ih*ihd), "w_kI" (d, ihd), "kI_norm", "kI_bias" (ihd,), "w_w"
(d, ih)}``; ``ffn`` ``{"w1", "w3", "w2"}`` or ``{"router", "w1", "w3",
"w2" (count, ...), "shared_w1", "shared_w3", "shared_w2"}``; ``hc_*``
``{"phi_pre", "phi_post" (n d, n), "phi_res" (n d, n n), "alpha" (3,),
"b_pre", "b_post" (n,), "b_res" (n n,)}``. All read as float32.

Three departures serve `correct` and its controls: ``operands="bfloat16"``
rounds each matmul's left operand to bfloat16 and, in the attention and
the indexer, the queries, keys, values and the softmax's numerator (the
stated precision; the router's, the maps' and the head's matmuls stay
float32, as stated); ``sum_chunk=n`` also keeps the running sums of the
indexer's scores, of the attention's two products and of the held
experts' matmuls in bfloat16, rounded after every ``n`` products: one step
below it; ``routing`` (rows, expert layers, seq, top_k) and ``selection``
(rows, full layers, seq, index_topk; -1 past t + 1) name the experts each
token goes to and the keys each query attends to, in the place of this
file's own choices (its scores and weights stay its own). `forward` also
returns its own top-k of each, so `compare` counts the tokens and keys it
would not have chosen.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 2048  # queries whose attention scores exist at one time
INDEX_BLOCK = 256   # queries whose index scores exist at one time
HEAD_CHUNK = 2048   # tokens whose logits exist at one time
FFN_ROWS = 4096     # tokens whose dense FFN activations exist at one time
SINKHORN = 20


def _round(x, operands):
    return x.astype(jnp.bfloat16).astype(F32) if operands == "bfloat16" else x


def _stored(x, operands):
    """``x`` rounded as `_round` rounds it, kept in bfloat16 where that
    holds the same numbers in half the bytes (a window's queries, keys and
    values beside 6 GiB of weights)."""
    return x.astype(jnp.bfloat16) if operands == "bfloat16" else x


def _mm(x, w, operands="float32"):
    return jnp.dot(_round(x.astype(F32), operands), w.astype(F32), precision="highest")


def _mm_bf16_sums(x, w, chunk):
    """x w with bfloat16 operands and a bfloat16 accumulator: the running
    sum is rounded to bfloat16 after every ``chunk`` products (inside a
    chunk they add up in float32, as one pass of a matrix unit does). (A
    loop over the contraction: the control's own departure from "no loop
    primitive", like its precision.)"""
    k = x.shape[-1]
    chunk = min(int(chunk), k)
    pad = (-k) % chunk
    xb = jnp.pad(x.astype(jnp.bfloat16).astype(F32), ((0, 0), (0, pad)))
    w = jnp.pad(w.astype(jnp.bfloat16).astype(F32), ((0, pad), (0, 0)))

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


def rope(x, theta):
    """(..., seq, width), rotate-half pairs ``(i, i + width/2)``."""
    hd, seq = x.shape[-1], x.shape[-2]
    inv = F32(theta) ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(seq, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def spec_of(config):
    """The configuration's numbers a layer needs, hashable, read under the
    published key names. What this file does not compute raises."""
    if int(config.get("n_group") or 1) != 1 or int(config.get("topk_group") or 1) != 1:
        raise ValueError("n_group / topk_group other than 1: not this file's mathematics")
    rope_ = config.get("rope_parameters") or {}
    if rope_.get("rope_type", "default") != "default" or config.get("topk_method"):
        raise ValueError("rope scaling or a correction bias: not this file's mathematics")
    return (
        ("heads", int(config["num_attention_heads"])),
        ("rkv", int(config["kv_lora_rank"])),
        ("dn", int(config["qk_nope_head_dim"])), ("dr", int(config["qk_rope_head_dim"])),
        ("dv", int(config["v_head_dim"])),
        ("theta", float(rope_.get("rope_theta", config.get("rope_theta", 10000.0)))),
        ("eps", float(config["rms_norm_eps"])),
        ("ih", int(config["index_n_heads"])), ("ihd", int(config["index_head_dim"])),
        ("topk", int(config["index_topk"])),
        ("top_k", int(config["num_experts_per_tok"])),
        ("num_experts", int(config["n_routed_experts"])),
        ("norm_topk", bool(config.get("norm_topk_prob", True))),
        ("scale", float(config.get("routed_scaling_factor", 1.0))),
        ("limit", float(config.get("swiglu_limit") or 0.0)),
        ("n", int(config["hc_mult"])), ("magnitude", float(config.get("hc_magnitude", 2.0))),
        ("hc_eps", float(config.get("hc_eps", 1e-6))),
    )


@functools.partial(jax.jit, static_argnames=("spec",))
def hc_maps(X, p, *, spec):
    """(a_pre, a_post, M) of a sublayer's hyper-connection over the streams
    X (rows, seq, n, d)."""
    c = dict(spec)
    n = c["n"]
    flat = X.reshape(X.shape[:2] + (-1,))
    x = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + c["eps"])
    alpha = p["alpha"].astype(F32)
    pre = jax.nn.sigmoid(alpha[0] * _mm(x, p["phi_pre"]) + p["b_pre"].astype(F32))
    post = c["magnitude"] * jax.nn.sigmoid(
        alpha[1] * _mm(x, p["phi_post"]) + p["b_post"].astype(F32))
    m = jnp.exp(alpha[2] * _mm(x, p["phi_res"]) + p["b_res"].astype(F32))
    m = m.reshape(X.shape[:2] + (n, n))
    for _ in range(SINKHORN):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + c["hc_eps"])
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + c["hc_eps"])
    return pre, post, m


@functools.partial(jax.jit, static_argnames=("spec",))
def hc_read_in(X, gain, pre, *, spec):
    u = jnp.sum(pre[..., None] * X, axis=-2)
    return rms_norm(u, gain, dict(spec)["eps"])


@jax.jit
def hc_write_out(X, m, post, y):
    return jnp.einsum("rtij,rtjd->rtid", m, X, precision="highest") + post[..., None] * y[:, :, None, :]


@functools.partial(jax.jit, static_argnames=("spec",))
def hc_read_out(X, phi, alpha, bias, *, spec):
    flat = X.reshape(X.shape[:2] + (-1,))
    x = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + dict(spec)["eps"])
    a = jax.nn.sigmoid(alpha.astype(F32)[0] * _mm(x, phi) + bias.astype(F32))
    return jnp.sum(a[..., None] * X, axis=-2)


@functools.partial(jax.jit, static_argnames=("spec", "operands"))
def mla_project(u, p, *, spec, operands):
    """One row's queries, keys and values (heads, seq, width) but ``k_r``
    (seq, dr), RoPE applied, rounded as the kernel's operands are; and the
    query latent ``c_q``."""
    c = dict(spec)
    heads, dn, rkv = c["heads"], c["dn"], c["rkv"]

    def by_head(x):  # (seq, heads * w) -> (heads, seq, w)
        return jnp.swapaxes(x.reshape(x.shape[0], heads, -1), 0, 1)

    c_q = rms_norm(_mm(u, p["w_qa"], operands), p["q_a_norm"], c["eps"])
    q = by_head(_mm(c_q, p["w_qb"], operands))
    kva = _mm(u, p["w_kva"], operands)
    c_kv = rms_norm(kva[:, :rkv], p["kv_a_norm"], c["eps"])
    kv = by_head(_mm(c_kv, p["w_kvb"], operands))
    q_r = rope(q[..., dn:], c["theta"])
    k_r = rope(kva[:, rkv:], c["theta"])
    return c_q, tuple(_stored(a, operands) for a in (
        q[..., :dn], q_r, kv[..., :dn], k_r, kv[..., dn:]))


@functools.partial(jax.jit, static_argnames=("spec", "operands"))
def index_project(u, c_q, p, *, spec, operands):
    """One row's indexer queries (seq, ih, ihd), keys (seq, ihd) and head
    weights (seq, ih)."""
    c = dict(spec)
    ih, ihd, dr = c["ih"], c["ihd"], c["dr"]
    q = _mm(c_q, p["w_qI"], operands).reshape(-1, ih, ihd)
    q_r = jnp.swapaxes(rope(jnp.swapaxes(q[..., :dr], 0, 1), c["theta"]), 0, 1)
    q = jnp.concatenate([q_r, q[..., dr:]], axis=-1)
    k = _mm(u, p["w_kI"], operands)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = (k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + c["eps"])
         * p["kI_norm"].astype(F32) + p["kI_bias"].astype(F32))
    k = jnp.concatenate([rope(k[:, :dr], c["theta"]), k[:, dr:]], axis=-1)
    w = _mm(u, p["w_w"], operands) / math.sqrt(ih)
    return _round(q, operands), _round(k, operands), w


@functools.partial(jax.jit, static_argnames=("spec", "sum_chunk"))
def index_top(q, k, w, first, *, spec, sum_chunk):
    """A block of queries' index scores against every key, and their own
    top-k (block, topk) int32, -1 past t + 1."""
    c = dict(spec)
    seq, topk = k.shape[0], min(c["topk"], k.shape[0])
    scale = 1.0 / math.sqrt(c["ihd"])
    total = jnp.zeros((q.shape[0], seq), F32)
    for j in range(c["ih"]):
        if sum_chunk:
            s = _mm_bf16_sums(q[:, j], k.T, sum_chunk)
            total = (total + w[:, j:j + 1] * jax.nn.relu(s * scale)).astype(
                jnp.bfloat16).astype(F32)
        else:
            s = jnp.dot(q[:, j], k.T, precision="highest")
            total = total + w[:, j:j + 1] * jax.nn.relu(s * scale)
    pos = first + jnp.arange(q.shape[0])
    causal = pos[:, None] >= jnp.arange(seq)[None, :]
    order = jnp.argsort(jnp.where(causal, -total, jnp.inf), axis=-1)[:, :topk]
    return jnp.where(jnp.arange(topk)[None, :] <= pos[:, None], order, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("seq",))
def selection_mask(keys, *, seq):
    """(block, seq) bool: True where a query's keys name the key."""
    at = jnp.where(keys < 0, seq, keys)
    rows = jnp.arange(keys.shape[0])[:, None]
    return jnp.zeros((keys.shape[0], seq), bool).at[rows, at].set(True, mode="drop")


@functools.partial(jax.jit, static_argnames=("operands", "sum_chunk"))
def attend(q_n, q_r, k_n, k_r, v, mask, sink, *, operands, sum_chunk):
    """One head's block of queries against the keys its mask selects, with
    the head's sink in the denominator."""
    q = jnp.concatenate([q_n, q_r], axis=-1).astype(F32)
    k = jnp.concatenate([k_n, k_r], axis=-1).astype(F32)  # the shared k_r, for this head too
    v = v.astype(F32)
    if sum_chunk:
        s = _mm_bf16_sums(q, k.T, sum_chunk)
    else:
        s = jnp.dot(q, k.T, precision="highest")
    s = jnp.where(mask, s / math.sqrt(q.shape[-1]), -jnp.inf)
    top = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sink)
    e = jnp.exp(s - top)
    total = jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - top)
    if sum_chunk:
        return _mm_bf16_sums(e, v, sum_chunk) / total
    return jnp.dot(_round(e, operands), v, precision="highest") / total


@functools.partial(jax.jit, static_argnames=("operands",))
def gate_out(u, att, p, *, operands):
    g = jax.nn.sigmoid(_mm(u, p["w_g"], operands))
    return _mm(att * g, p["wo"], operands)


def attention(u, p, keys, *, spec, operands, sum_chunk):
    """The sublayer's output for (rows, seq, d): ``keys`` (rows, seq, topk)
    the keys each query attends to. Returns (y, own keys or None): a full
    layer's own top-k is computed whatever ``keys`` says."""
    c = dict(spec)
    out, owns = [], []
    for r in range(u.shape[0]):
        c_q, (q_n, q_r, k_n, k_r, v) = mla_project(u[r], p, spec=spec, operands=operands)
        seq = q_n.shape[1]
        if "w_qI" in p:
            qI, kI, w = index_project(u[r], c_q, p, spec=spec, operands=operands)
            owns.append(jnp.concatenate([
                index_top(qI[lo:lo + INDEX_BLOCK], kI, w[lo:lo + INDEX_BLOCK], lo,
                          spec=spec, sum_chunk=sum_chunk)
                for lo in range(0, seq, INDEX_BLOCK)]))
        row_keys = owns[-1] if keys is None else keys[r]
        blocks = []
        for lo in range(0, seq, QUERY_BLOCK):
            mask = selection_mask(row_keys[lo:lo + QUERY_BLOCK], seq=seq)
            att = jnp.concatenate([
                attend(q_n[a, lo:lo + QUERY_BLOCK], q_r[a, lo:lo + QUERY_BLOCK], k_n[a], k_r,
                       v[a], mask, p["sink"][a].astype(F32), operands=operands,
                       sum_chunk=sum_chunk)
                for a in range(c["heads"])], axis=-1)
            blocks.append(gate_out(u[r, lo:lo + QUERY_BLOCK], att, p, operands=operands))
        out.append(jnp.concatenate(blocks))
    return jnp.stack(out), (jnp.stack(owns) if owns else None)


def swiglu(u, w1, w3, w2, operands, limit, mm_experts=None):
    mm = mm_experts or functools.partial(_mm, operands=operands)
    g, up = mm(u, w1), mm(u, w3)
    if limit:
        g, up = jnp.minimum(g, limit), jnp.clip(up, -limit, limit)
    return mm(jax.nn.silu(g) * up, w2)


@functools.partial(jax.jit, static_argnames=("spec", "operands"))
def dense_ffn(u, f, *, spec, operands):
    return swiglu(u, f["w1"], f["w3"], f["w2"], operands, dict(spec)["limit"])


def dense_rows(u, f, *, spec, operands):
    """`dense_ffn` over (rows, seq, d), `FFN_ROWS` tokens at a time."""
    x = u.reshape(-1, u.shape[-1])
    return jnp.concatenate([
        dense_ffn(x[lo:lo + FFN_ROWS], f, spec=spec, operands=operands)
        for lo in range(0, x.shape[0], FFN_ROWS)]).reshape(u.shape)


@functools.partial(jax.jit, static_argnames=("spec",))
def route(u, router, routing, *, spec):
    """(weight of every expert on every token (n, E), 0 where not routed,
    load (rows, E), own top-k (rows, seq, top_k))."""
    c = dict(spec)
    rows, seq, d = u.shape
    x = u.reshape(rows * seq, d)
    s = jax.nn.sigmoid(_mm(x, router))  # float32 operands, as stated
    _, own = jax.lax.top_k(s, c["top_k"])
    idx = own if routing is None else routing.reshape(rows * seq, c["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, c["num_experts"], dtype=F32), axis=1)  # 0/1
    w = s * chosen
    if c["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    load = jnp.sum(chosen.reshape(rows, seq, -1), axis=1).astype(jnp.int32)
    return w * c["scale"], load, own.reshape(rows, seq, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("spec", "operands", "sum_chunk"))
def add_expert(out, x, w1, w3, w2, weight, *, spec, operands, sum_chunk=0):
    """``out + weight * SwiGLU(x)``: one expert on every token, masked by
    its weight (0 on a token not routed to it)."""
    mm = functools.partial(_mm_bf16_sums, chunk=sum_chunk) if sum_chunk else None
    return out + weight[:, None] * swiglu(x, w1, w3, w2, operands, dict(spec)["limit"], mm)


def experts(u, f, routing, *, spec, held, operands, sum_chunk):
    """(FFN(u), load, own top-k): every held expert in a plain loop, then
    the shared expert."""
    first, count = held
    w, load, own = route(u, f["router"], routing, spec=spec)
    x = u.reshape(-1, u.shape[-1])
    out = jnp.zeros_like(x)
    for e in range(count):
        out = add_expert(out, x, f["w1"][e], f["w3"][e], f["w2"][e], w[:, first + e],
                         spec=spec, operands=operands, sum_chunk=sum_chunk)
    if "shared_w1" in f:  # every token, whatever is held
        out = add_expert(out, x, f["shared_w1"], f["shared_w3"], f["shared_w2"],
                         jnp.ones(x.shape[:1], F32), spec=spec, operands=operands)
    return out.reshape(u.shape), load, own


@functools.partial(jax.jit, static_argnames=("eps",))
def head_chunk(h, final_norm, w_head, target, *, eps):
    logits = _mm(rms_norm(h, final_norm, eps), w_head)  # float32: enable_lm_head_fp32
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]


def head(h, final_norm, w_head, tokens, *, eps):
    rows, seq, d = h.shape
    target = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1).reshape(-1)
    flat = h.reshape(rows * seq, d)
    lp = jnp.concatenate([
        head_chunk(flat[lo:lo + HEAD_CHUNK], final_norm, w_head,
                   target[lo:lo + HEAD_CHUNK], eps=eps)
        for lo in range(0, rows * seq, HEAD_CHUNK)
    ]).reshape(rows, seq)
    return lp.at[:, -1].set(0.0)


def forward(config, weights, tokens, held=None, operands="float32",
            sum_chunk=0, routing=None, selection=None):
    """(token_logprob (rows, seq) float32, expert_load (rows, expert
    layers, n_routed_experts) int32, own top-k experts (rows, expert layers,
    seq, top_k) int32, own keys (rows, full layers, seq, index_topk) int32)
    of ``tokens`` (rows, seq), layer by layer and part by part."""
    held = tuple(held or (0, int(config["n_routed_experts"])))
    spec = spec_of(config)
    c = dict(spec)
    tokens = jnp.asarray(tokens, jnp.int32)
    dense = list(config["mlp_layer_types"]).count("dense")
    full = [t == "full" for t in config["indexer_types"]]
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens].astype(F32)
        X = jnp.broadcast_to(h[:, :, None, :], h.shape[:2] + (c["n"], h.shape[-1]))
        loads, owns, own_keys, keys = [], [], [], None
        for i in range(int(config["num_hidden_layers"])):
            w = weights["layers"][i]
            pre, post, m = hc_maps(X, w["hc_op"], spec=spec)
            u = hc_read_in(X, w["op_norm"], pre, spec=spec)
            if full[i]:
                keys = None
                if selection is not None:
                    keys = jnp.asarray(selection, jnp.int32)[:, sum(full[:i])]
            y, own = attention(u, w["op"], keys, spec=spec, operands=operands,
                               sum_chunk=sum_chunk)
            if own is not None:
                own_keys.append(own)
                keys = own if keys is None else keys
            X = hc_write_out(X, m, post, y)
            pre, post, m = hc_maps(X, w["hc_ffn"], spec=spec)
            u = hc_read_in(X, w["ffn_norm"], pre, spec=spec)
            if i < dense:
                y = dense_rows(u, w["ffn"], spec=spec, operands=operands)
            else:
                forced = None
                if routing is not None:
                    forced = jnp.asarray(routing, jnp.int32)[:, i - dense]
                y, load, own = experts(u, w["ffn"], forced, spec=spec, held=held,
                                       operands=operands, sum_chunk=sum_chunk)
                loads.append(load)
                owns.append(own)
            X = hc_write_out(X, m, post, y)
        h = hc_read_out(X, weights["hc_head_phi"], weights["hc_head_alpha"],
                        weights["hc_head_bias"], spec=spec)
        lp = head(h, weights["final_norm"], weights["head"], tokens, eps=c["eps"])
    return lp, jnp.stack(loads, axis=1), jnp.stack(owns, axis=1), jnp.stack(own_keys, axis=1)


NUMBERS = ("logprob_p99_abs_err", "routing_swapped_share", "index_swapped_share",
           "expert_load_l1_share")


def _keys_valid(keys):
    """Query t names min(t + 1, topk) distinct keys s <= t, then -1s."""
    seq, topk = keys.shape[-2], keys.shape[-1]
    t = np.arange(seq)[:, None]
    named = np.arange(topk)[None, :] <= t
    if np.any(keys[..., ~named] != -1) or np.any((keys < 0) & named) or np.any(keys > t):
        return False
    ordered = np.sort(np.where(named, keys, -1 - np.arange(topk)), axis=-1)
    return not (np.diff(ordered, axis=-1) == 0).any()


def compare(got, want, top_k):
    """The four numbers `correct` is decided on, over the checked rows.
    ``got`` is what the program gave, (token_logprob, expert_load,
    expert_choice, index_choice); ``want`` what `forward` gives for the
    same rows ALONG THE PROGRAM'S ROUTING AND SELECTION. The 99th
    percentile of |log-probability error| over the scored positions; the
    share of (token, expert layer) pairs whose experts are not the
    reference's own top-k there; the share of the (query, full layer)
    selected keys that are not in the reference's own top-k there; the L1
    distance of ``expert_load`` from the counts of the routing over the
    routed rows. A wrong shape, a NaN, a token without ``top_k`` distinct
    experts of the model, or a query whose keys are not min(t + 1, topk)
    distinct keys s <= t, reads as infinite."""
    (got_lp, got_ld, got_ch, got_ix), (want_lp, want_ld, own, own_ix) = (
        [np.asarray(a) for a in side] for side in (got, want)
    )
    bad = dict.fromkeys(NUMBERS, float("inf"))
    if (got_lp.shape != want_lp.shape or got_ld.shape != want_ld.shape
            or got_ch.shape != own.shape or got_ch.shape[-1] != top_k
            or got_ix.shape != own_ix.shape):
        return bad
    ch = np.sort(got_ch.astype(np.int64), axis=-1)
    if ch.min() < 0 or ch.max() >= got_ld.shape[-1] or (np.diff(ch, axis=-1) == 0).any():
        return bad
    ix = got_ix.astype(np.int64)
    if not _keys_valid(ix):
        return bad
    err = np.abs(got_lp.astype(np.float64) - want_lp.astype(np.float64))
    if np.isnan(err).any() or np.any(got_lp[:, -1] != 0.0):
        return bad
    swapped = np.any(ch != np.sort(own.astype(np.int64), axis=-1), axis=-1)
    routed = want_lp.shape[0] * want_lp.shape[1] * top_k * want_ld.shape[1]
    seq = ix.shape[-2]
    flat = lambda a: (np.arange(a.size // a.shape[-1]).reshape(a.shape[:-1] + (1,))
                      * (seq + 1) + a)[a >= 0]
    named = flat(ix)
    return {
        "logprob_p99_abs_err": float(np.percentile(err[:, :-1], 99)),
        "routing_swapped_share": float(np.mean(swapped)),
        "index_swapped_share": float(np.mean(~np.isin(named, flat(own_ix.astype(np.int64))))),
        "expert_load_l1_share":
            float(np.sum(np.abs(got_ld.astype(np.int64) - want_ld))) / routed,
    }
