"""Plain reference of `trinity-mini`: the forward pass in straightforward
`jax.numpy`, float32, every matmul at precision "highest"; nothing of the
package, no kernel, no grouped matmul, no scan over stacked weights, no
cache. `tests/references/trinity.py` is this file, letter for letter (a
tier-1 test compares the two).

The model (arcee-ai/Trinity-Mini config.json, ``model_type: afmoe``, read
under its own key names; d = hidden_size, ``u`` the normed input of a
sublayer):

- ``h0 = E[tokens] * sqrt(d)`` (``mup_enabled``).
- layer i: ``r = h + RMSNorm_post_op(Op(RMSNorm_op(h)))``, ``h' = r +
  RMSNorm_post_ffn(FFN(RMSNorm_ffn(r)))``: four gains a layer.
- Op, by ``layer_types[i]``: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``
  by heads of ``head_dim``; an RMSNorm over each q and each k head; on a
  "sliding_attention" layer ONLY, rotate-half RoPE (``rope_theta``) on q
  and k ("full_attention" layers have no positional encoding); softmax(q
  k^T / sqrt(head_dim)) v over the keys s a query t sees, each key/value
  head serving ``heads // kv_heads`` consecutive query heads: s <= t on a
  full layer, ``t - sliding_window < s <= t`` on a sliding one (the window's
  W keys, its own among them); ``y = (o * sigmoid(u W_g)) W_o``, the gate
  elementwise over the heads' output.
- FFN of the first ``num_dense_layers`` layers: SwiGLU, ``(silu(u W1) * u
  W3) W2``. Of the others: ``s = sigmoid(u W_r)`` over all ``num_experts``
  in float32; the top ``num_experts_per_tok`` of ``s + b`` (``b`` the
  expert bias, for choosing only); weights ``s`` at the chosen over (their
  sum + 1e-20) times ``route_scale`` (``route_norm``); ``sum_k w_k
  SwiGLU_{e_k}(u) + SwiGLU_shared(u)``, every SwiGLU of
  ``moe_intermediate_size``. EVERY held expert is evaluated on every token
  in a plain loop and masked by its weight; ``held = (first, count)``: only
  those experts' weights are there and only their part is computed, the
  shared expert whatever is held.
- ends: ``logits = RMSNorm(h_L) W_head`` (untied), a chunk of tokens at a
  time; ``token_logprob[t] = log_softmax(logits[t])[tokens[t+1]]``, the
  last 0.

Attention is taken a head and a block of queries at a time against every
key of the row, masked: a window's score matrix in float32 does not fit
beside the weights.

Weights, in this file's own layout: ``{"embed" (V, d), "head" (d, V),
"final_norm" (d,), "layers": [...]}``, a layer being ``{"op_norm",
"op_post_norm", "ffn_norm", "ffn_post_norm" (d,), "op": {"wq" (d,
heads*hd), "wk", "wv" (d, kv_heads*hd), "wg" (d, heads*hd), "q_norm",
"k_norm" (hd,), "wo" (heads*hd, d)}, "ffn": ...}`` with ``ffn`` ``{"w1",
"w3" (d, f), "w2" (f, d)}`` or ``{"router" (d, E), "bias" (E,), "w1", "w3"
(count, d, fe), "w2" (count, fe, d), "shared_w1", "shared_w3" (d, fe),
"shared_w2" (fe, d)}``. ``layers`` is anything indexed by the layer's
number (one that makes a layer when asked for it keeps one layer on the
device at a time). All are read as float32.

Three departures serve `correct` and its controls: ``operands="bfloat16"``
rounds each matmul's left operand to bfloat16 and, in attention, the
queries, keys, values and the softmax's numerator (the stated precision;
the router's matmul stays float32, as stated); ``sum_chunk=n`` also keeps
the running sum of every other matmul in bfloat16 (the projections, both
products of attention, the gate, the FFNs, every expert, the head),
rounded after every ``n`` products: one step below it; ``routing`` (rows,
expert layers, seq, top_k) names the experts each token goes to in the
place of this file's own top-k (its scores and weights stay its own).
`forward` also returns its own top-k, so `compare` counts the tokens it
would not have chosen.
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 2048  # queries whose scores exist at one time, a head at a time
HEAD_CHUNK = 1024   # tokens whose logits exist at one time
FFN_ROWS = 4096     # tokens whose dense FFN activations exist at one time


def _round(x, operands):
    return x.astype(jnp.bfloat16).astype(F32) if operands == "bfloat16" else x


def _mm(x, w, operands="float32", sum_chunk=0):
    if sum_chunk:
        return _mm_bf16_sums(x, w, sum_chunk)
    return jnp.dot(_round(x.astype(F32), operands), w.astype(F32), precision="highest")


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


def spec_of(config):
    """The configuration's numbers a layer needs, hashable, read under the
    published key names. What this file does not compute raises."""
    for key in ("n_group", "topk_group", "num_expert_groups", "num_limited_groups"):
        if int(config.get(key) or 1) != 1:
            raise ValueError(f"{key} = {config[key]}: not this file's mathematics")
    for key, want in (("score_func", "sigmoid"), ("hidden_act", "silu"),
                      ("rope_scaling", None), ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"{key} = {config[key]!r}: not this file's mathematics")
    heads = int(config["num_attention_heads"])
    return (
        ("heads", heads), ("kv_heads", int(config["num_key_value_heads"])),
        ("head_dim", int(config.get("head_dim") or config["hidden_size"] // heads)),
        ("theta", float(config["rope_theta"])), ("eps", float(config["rms_norm_eps"])),
        ("window", int(config["sliding_window"])),
        ("top_k", int(config["num_experts_per_tok"])),
        ("num_experts", int(config["num_experts"])),
        ("norm_topk", bool(config.get("route_norm", True))),
        ("scale", float(config.get("route_scale", 1.0))),
    )


@functools.partial(jax.jit, static_argnames=("spec", "sliding", "operands", "sum_chunk"))
def project(u, p, *, spec, sliding, operands, sum_chunk):
    """One row's queries (heads, seq, hd), keys and values (kv_heads, seq,
    hd): normed by head, rotated on a sliding layer, rounded as the
    kernel's operands are."""
    c = dict(spec)

    def heads_of(w, n):  # (n, seq, hd)
        return jnp.swapaxes(
            _mm(u, w, operands, sum_chunk).reshape(u.shape[0], n, c["head_dim"]), 0, 1)

    q = rms_norm(heads_of(p["wq"], c["heads"]), p["q_norm"], c["eps"])
    k = rms_norm(heads_of(p["wk"], c["kv_heads"]), p["k_norm"], c["eps"])
    if sliding:
        q, k = rope(q, c["theta"]), rope(k, c["theta"])
    return tuple(_round(a, operands) for a in (q, k, heads_of(p["wv"], c["kv_heads"])))


@functools.partial(jax.jit, static_argnames=("window", "operands", "sum_chunk"))
def attend(q, k, v, first, *, window, operands, sum_chunk):
    """One head's block of queries from position ``first`` against every
    key of the row: s <= t, and t - window < s where ``window`` is given."""
    s = _mm(q, k.T, operands, sum_chunk) / math.sqrt(q.shape[-1])
    t = first + jnp.arange(q.shape[0])[:, None] - jnp.arange(k.shape[0])[None, :]
    seen = t >= 0 if window is None else (t >= 0) & (t < window)
    s = jnp.where(seen, s, -jnp.inf)
    # softmax(s) v as (e v) / sum(e), e = exp(s - max s): e is the left
    # operand of a matmul, and is rounded as one
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    total = jnp.sum(e, axis=-1, keepdims=True)
    return _mm(e, v, operands, sum_chunk) / total


@functools.partial(jax.jit, static_argnames=("operands", "sum_chunk"))
def gate_out(u, att, p, *, operands, sum_chunk):
    g = jax.nn.sigmoid(_mm(u, p["wg"], operands, sum_chunk))
    return _mm(att * g, p["wo"], operands, sum_chunk)


def attention(u, p, *, spec, sliding, operands, sum_chunk):
    """The sublayer's output for (rows, seq, d), a row, a head and a block
    of queries at a time."""
    c = dict(spec)
    group = c["heads"] // c["kv_heads"]
    window = c["window"] if sliding else None
    out = []
    for r in range(u.shape[0]):
        q, k, v = project(u[r], p, spec=spec, sliding=sliding, operands=operands,
                          sum_chunk=sum_chunk)
        seq = q.shape[1]
        att = jnp.concatenate([
            jnp.concatenate([
                attend(q[a, lo:lo + QUERY_BLOCK], k[a // group], v[a // group], lo,
                       window=window, operands=operands, sum_chunk=sum_chunk)
                for lo in range(0, seq, QUERY_BLOCK)])
            for a in range(c["heads"])], axis=-1)  # (seq, heads * hd), head-major
        out.append(gate_out(u[r], att, p, operands=operands, sum_chunk=sum_chunk))
    return jnp.stack(out)


def swiglu(u, w1, w3, w2, operands, sum_chunk=0):
    mm = functools.partial(_mm, operands=operands, sum_chunk=sum_chunk)
    return mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2)


@functools.partial(jax.jit, static_argnames=("operands", "sum_chunk"))
def dense_ffn(u, f, *, operands, sum_chunk):
    return swiglu(u, f["w1"], f["w3"], f["w2"], operands, sum_chunk)


def dense_rows(u, f, *, operands, sum_chunk):
    """`dense_ffn` over (rows, seq, d), `FFN_ROWS` tokens at a time."""
    x = u.reshape(-1, u.shape[-1])
    return jnp.concatenate([
        dense_ffn(x[lo:lo + FFN_ROWS], f, operands=operands, sum_chunk=sum_chunk)
        for lo in range(0, x.shape[0], FFN_ROWS)]).reshape(u.shape)


@functools.partial(jax.jit, static_argnames=("spec",))
def route(u, router, bias, routing, *, spec):
    """(weight of every expert on every token (n, E), 0 where not routed,
    load (rows, E), own top-k (rows, seq, top_k))."""
    c = dict(spec)
    rows, seq, d = u.shape
    x = u.reshape(rows * seq, d)
    s = jax.nn.sigmoid(_mm(x, router))  # float32 operands, as stated
    _, own = jax.lax.top_k(s + bias.astype(F32), c["top_k"])
    idx = own if routing is None else routing.reshape(rows * seq, c["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, c["num_experts"], dtype=F32), axis=1)  # 0/1
    w = s * chosen
    if c["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    load = jnp.sum(chosen.reshape(rows, seq, -1), axis=1).astype(jnp.int32)
    return w * c["scale"], load, own.reshape(rows, seq, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("operands", "sum_chunk"))
def add_expert(out, x, w1, w3, w2, weight, *, operands, sum_chunk=0):
    """``out + weight * SwiGLU(x)``: one expert on every token, masked by
    its weight (0 on a token not routed to it)."""
    return out + weight[:, None] * swiglu(x, w1, w3, w2, operands, sum_chunk)


def experts(u, f, routing, *, spec, held, operands, sum_chunk):
    """(FFN(u), load, own top-k): every held expert in a plain loop, then
    the shared expert."""
    first, count = held
    w, load, own = route(u, f["router"], f["bias"], routing, spec=spec)
    x = u.reshape(-1, u.shape[-1])
    out = jnp.zeros_like(x)
    for e in range(count):
        out = add_expert(out, x, f["w1"][e], f["w3"][e], f["w2"][e], w[:, first + e],
                         operands=operands, sum_chunk=sum_chunk)
    out = add_expert(out, x, f["shared_w1"], f["shared_w3"], f["shared_w2"],
                     jnp.ones(x.shape[:1], F32), operands=operands, sum_chunk=sum_chunk)
    return out.reshape(u.shape), load, own


@functools.partial(jax.jit, static_argnames=("eps",))
def norm(x, gain, *, eps):
    return rms_norm(x, gain, eps)


@functools.partial(jax.jit, static_argnames=("eps", "operands", "sum_chunk"))
def head_chunk(h, final_norm, w_head, target, *, eps, operands, sum_chunk):
    logits = _mm(rms_norm(h, final_norm, eps), w_head, operands, sum_chunk)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]


def head(h, final_norm, w_head, tokens, *, eps, operands, sum_chunk):
    rows, seq, d = h.shape
    target = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1).reshape(-1)
    flat = h.reshape(rows * seq, d)
    lp = jnp.concatenate([
        head_chunk(flat[lo:lo + HEAD_CHUNK], final_norm, w_head,
                   target[lo:lo + HEAD_CHUNK], eps=eps, operands=operands,
                   sum_chunk=sum_chunk)
        for lo in range(0, rows * seq, HEAD_CHUNK)
    ]).reshape(rows, seq)
    return lp.at[:, -1].set(0.0)


def forward(config, weights, tokens, held=None, operands="float32",
            sum_chunk=0, routing=None):
    """(token_logprob (rows, seq) float32, expert_load (rows, expert
    layers, num_experts) int32, own top-k (rows, expert layers, seq, top_k)
    int32) of ``tokens`` (rows, seq), layer by layer: what is on the device
    at one time is one layer's weights and one layer's activations."""
    held = tuple(held or (0, int(config["num_experts"])))
    spec = spec_of(config)
    c = dict(spec)
    eps = c["eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    dense = int(config["num_dense_layers"])
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens].astype(F32)
        if config.get("mup_enabled"):
            h = h * math.sqrt(int(config["hidden_size"]))
        loads, owns = [], []
        for i, kind in enumerate(config["layer_types"]):
            w = weights["layers"][i]
            u = norm(h, w["op_norm"], eps=eps)
            y = attention(u, w["op"], spec=spec, sliding=kind == "sliding_attention",
                          operands=operands, sum_chunk=sum_chunk)
            h = h + norm(y, w["op_post_norm"], eps=eps)
            u = norm(h, w["ffn_norm"], eps=eps)
            if i < dense:
                y = dense_rows(u, w["ffn"], operands=operands, sum_chunk=sum_chunk)
            else:
                forced = None
                if routing is not None:
                    forced = jnp.asarray(routing, jnp.int32)[:, i - dense]
                y, load, own = experts(u, w["ffn"], forced, spec=spec, held=held,
                                       operands=operands, sum_chunk=sum_chunk)
                loads.append(load)
                owns.append(own)
            h = h + norm(y, w["ffn_post_norm"], eps=eps)
            del w, u, y
        lp = head(h, weights["final_norm"], weights["head"], tokens, eps=eps,
                  operands=operands, sum_chunk=sum_chunk)
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
    import numpy as np

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
