"""Plain reference of `nemotron-3-super-120b-a12b`: the forward pass in
straightforward `jax.numpy`, float32, every matmul at precision "highest";
nothing of the package, no kernel, no chunked scan, no sort, no grouped
matmul, no stacked weights, no cache. `tests/references/nemotron_h.py` is
this file, letter for letter (a tier-1 test compares the two).

The model (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json,
`model_type: nemotron_h`, read under its own key names; d = hidden_size,
``u`` the normed input of a layer; no projection has a bias):

- layer i: ``h' = h + Mixer_i(RMSNorm_i(h))``, ONE mixer a layer, chosen by
  character i of ``hybrid_override_pattern``: "M" Mamba-2, "E" experts,
  "*" attention. One norm a layer (``layer_norm_epsilon``).
- "M" (``mamba_num_heads`` heads of ``mamba_head_dim``, inner width their
  product, ``n_groups`` groups, state ``ssm_state_size``, ``conv_kernel``):
  ``z, x, B, C, dt = u W_z, u W_x, u W_B, u W_C, u W_dt``; each of x, B, C
  through a causal depthwise convolution (``y_t = sum_j w[j] x_{t-(k-1-j)}
  + b``, zeros to the left) and SiLU; ``Δ = softplus(dt + dt_bias)`` (a
  scalar a head and token), ``A = -exp(A_log)`` (a scalar a head); the
  recurrence ONE POSITION AT A TIME, ``S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t
  ⊗ B_t`` (head width x state a head, ``S_0 = 0``; head j uses group
  ``j // (heads / groups)``), ``y_t = S_t C_t + D x_t``; the gate
  ``g = y silu(z)``, then an RMSNorm over each group's share of the inner
  width with a gain of the whole inner width; ``g W_out``.
- "*": ``q = u W_q`` (``num_attention_heads`` of ``head_dim``), ``k, v``
  (``num_key_value_heads``), causal softmax of ``q k^T / sqrt(head_dim)``,
  a key head serving consecutive query heads, ``o W_o``. A head and a block
  of queries at a time, so that 32,768 positions fit.
- "E": ``s = sigmoid(u W_r)`` over all ``n_routed_experts`` in float32; the
  ``num_experts_per_tok`` chosen are the top-k of ``s + b`` (correction
  bias, choice only; ``n_group`` = ``topk_group`` = 1); weights ``s`` at the
  chosen over their sum (``norm_topk_prob``), times
  ``routed_scaling_factor``. Routed experts live in a latent
  (``moe_latent_size``): ``x_l = u W_l1``, expert e is ``relu(x_l W1_e)^2
  W2_e`` (``mlp_hidden_act: relu2``, two matrices, not gated),
  ``y = (sum_k w_k E_{e_k}(x_l)) W_l2 + relu(u W_s1)^2 W_s2`` (one shared
  expert, ``moe_shared_expert_intermediate_size`` wide, on the residual
  width). EVERY held expert is evaluated on every token in a plain loop and
  masked by its weight, 0 where the token was not routed to it.
  ``held = (first, count)``: only those experts' weights are there and only
  their part of the sum is computed (it still goes through ``W_l2``); the
  shared expert is computed whatever is held (the share test counts it
  once).
- ends: ``h0 = E[tokens]``; logits ``= RMSNorm(h_L) W_head`` (untied), a
  chunk of tokens at a time; ``token_logprob[t] =
  log_softmax(logits[t])[tokens[t+1]]``, the last 0.

Departures from the published description, each for a stated reason:
- the multi-token-prediction module (``num_nextn_predict_layers`` 1,
  ``mtp_hybrid_override_pattern`` "*E") is left out: log p(token t+1 | <= t)
  does not pass through it (it predicts token t+2).
- attention applies no rotation and no q/k norm: the family's public
  modelling code applies none (position comes from the "M" layers); the
  config's ``rope_theta`` and ``partial_rotary_factor`` are unused.
- the gate comes before the grouped norm (the family's gated RMSNorm with
  ``norm_before_gate`` false); the latent projections have no bias.

Weights, in this file's own layout (the equations' names; nothing is
stacked or fused): ``{"embed" (vocabulary, d), "head" (d, vocabulary),
"final_norm" (d,), "layers": [...]}``, a layer being a mapping ``{"norm"
(d,), "mixer": ...}`` with ``mixer`` for "M" ``{"w_z", "w_x" (d, inner),
"w_B", "w_C" (d, groups*state), "w_dt" (d, heads), "conv_x" (k, inner),
"conv_B", "conv_C" (k, groups*state), "conv_bx" (inner,), "conv_bB",
"conv_bC" (groups*state,), "dt_bias", "A_log", "D" (heads,), "gate_norm"
(inner,), "w_out" (inner, d)}``, for "*" ``{"wq" (d, heads*hd), "wk", "wv"
(d, kv*hd), "wo" (heads*hd, d)}``, for "E" ``{"router" (d, experts), "bias"
(experts,), "w_l1" (d, latent), "w_l2" (latent, d), "w1" (count, latent,
fe), "w2" (count, fe, latent), "shared_w1" (d, fs), "shared_w2" (fs, d)}``.
``layers`` is anything indexed by the layer's number. All are read as
float32, so the reference and the program hold the same (bfloat16-rounded)
numbers.

Three departures serve `correct` and its controls, and nothing else:
``operands="bfloat16"`` rounds where the stated precision rounds: each
matmul's left operand; in the state-space layer x, B and C after their
SiLU, ``Δ_t x_t`` and y (the state stays float32, where it is carried and
where ``S_t C_t`` reads it: a chunked scan rounds it once a chunk and the
decayed scores besides, roundings a recurrence has no place for, and a
rounding at every position here would add noise of its own that the
program does not have); in the attention the queries, keys, values and
the softmax's numerator (the router's matmul stays in float32, as stated);
``sum_chunk=n`` also keeps the running sums of the expert matmuls in
bfloat16, rounded after every ``n`` products, and rounds the carried state
of the scan (the running sum of its products ``Δ x ⊗ B``) to bfloat16
after every ``n`` positions: one step below; and ``routing`` (rows, expert
layers, seq, top_k) names the experts each token goes to in the place of
this file's own top-k (the scores and weights stay its own): a rounded
residual stream swaps a token's k-th and (k+1)-th expert where their
scores are close, one swap moves every later number of the row, and so two
computations agree to their rounding only along one routing. `forward`
also returns its own top-k, so `compare` counts the tokens whose routing
it would not have chosen.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 2048  # queries whose scores exist at one time
HEAD_CHUNK = 2048   # tokens whose logits exist at one time
KINDS = "M*E"  # a layer's mixer: Mamba-2, attention, experts


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


def spec_of(config):
    """The configuration's numbers a layer needs, hashable, read under the
    published key names. What this file does not compute raises."""
    if int(config.get("n_group") or 1) != 1 or int(config.get("topk_group") or 1) != 1:
        raise ValueError("n_group / topk_group other than 1: not this file's mathematics")
    for key in ("use_bias", "mlp_bias", "attention_bias", "mamba_proj_bias"):
        if config.get(key):
            raise ValueError(f"{key}: not this file's mathematics")
    pattern = str(config["hybrid_override_pattern"])
    if set(pattern) - set(KINDS):
        raise ValueError("hybrid_override_pattern: layers are 'M', '*' or 'E' in this file")
    if config.get("mlp_hidden_act") != "relu2" or config.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError("mlp_hidden_act / mamba_hidden_act: relu2 and silu in this file")
    eps = {float(config[k]) for k in ("layer_norm_epsilon", "norm_eps") if k in config}
    if len(eps) != 1:
        raise ValueError("layer_norm_epsilon / norm_eps: one epsilon in this file")
    heads = int(config["num_attention_heads"])
    return (
        ("pattern", pattern), ("eps", eps.pop()),
        ("ssm_heads", int(config["mamba_num_heads"])),
        ("ssm_width", int(config["mamba_head_dim"])),
        ("groups", int(config["n_groups"])), ("state", int(config["ssm_state_size"])),
        ("kernel", int(config["conv_kernel"])),
        ("heads", heads), ("kv", int(config["num_key_value_heads"])),
        ("hd", int(config.get("head_dim") or config["hidden_size"] // heads)),
        ("top_k", int(config["num_experts_per_tok"])),
        ("num_experts", int(config["n_routed_experts"])),
        ("norm_topk", bool(config.get("norm_topk_prob", True))),
        ("scale", float(config.get("routed_scaling_factor", 1.0))),
    )


def causal_conv(x, w, b):
    """Depthwise over (seq, channels): ``y_t = sum_j w[j] x_{t-(k-1-j)} + b``,
    zeros before the first position; then SiLU."""
    k, seq = w.shape[0], x.shape[0]
    y = b.astype(F32)
    for j in range(k):
        back = k - 1 - j  # how far behind t this tap reads
        shifted = jnp.concatenate([jnp.zeros((back, x.shape[1]), F32), x[:seq - back]])
        y = y + w[j].astype(F32) * shifted
    return jax.nn.silu(y)


@functools.partial(jax.jit, static_argnames=("spec", "operands", "sum_chunk"))
def ssm(h, gain, p, *, spec, operands, sum_chunk=0):
    """``h + Mamba2(RMSNorm(h))`` of one row (seq, d): the recurrence one
    position at a time."""
    c = dict(spec)
    heads, width, groups, state = c["ssm_heads"], c["ssm_width"], c["groups"], c["state"]
    seq = h.shape[0]
    u = rms_norm(h, gain, c["eps"])
    z = _mm(u, p["w_z"], operands)
    x = _round(causal_conv(_mm(u, p["w_x"], operands), p["conv_x"], p["conv_bx"]), operands)
    B = _round(causal_conv(_mm(u, p["w_B"], operands), p["conv_B"], p["conv_bB"]), operands)
    C = _round(causal_conv(_mm(u, p["w_C"], operands), p["conv_C"], p["conv_bC"]), operands)
    dt = jax.nn.softplus(_mm(u, p["w_dt"], operands) + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))
    x = x.reshape(seq, heads, width)
    B, C = B.reshape(seq, groups, state), C.reshape(seq, groups, state)

    def position(S, at):
        t, x_t, dt_t, B_t, C_t = at
        # head j reads group j // (heads / groups)
        B_t = jnp.repeat(B_t, heads // groups, axis=0)
        C_t = jnp.repeat(C_t, heads // groups, axis=0)
        dx = _round(dt_t[:, None] * x_t, operands)  # (heads, width)
        S = jnp.exp(dt_t * A)[:, None, None] * S + dx[:, :, None] * B_t[:, None, :]
        if sum_chunk:  # the running sum of the products, in bfloat16
            S = jnp.where((t + 1) % sum_chunk == 0, _round(S, "bfloat16"), S)
        y_t = jnp.sum(S * C_t[:, None, :], axis=-1)
        return S, y_t + p["D"].astype(F32)[:, None] * x_t

    S0 = jnp.zeros((heads, width, state), F32)
    _, y = jax.lax.scan(position, S0, (jnp.arange(seq), x, dt, B, C))
    g = _round(y, operands).reshape(seq, heads * width) * jax.nn.silu(z)
    g = g.reshape(seq, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + c["eps"])
    g = g.reshape(seq, heads * width) * p["gate_norm"].astype(F32)
    return h + _mm(g, p["w_out"], operands)


@functools.partial(jax.jit, static_argnames=("spec", "operands"))
def attention_project(h, gain, p, *, spec, operands):
    """One row's queries, keys and values, (heads, seq, hd) each, rounded
    as the kernel's operands are."""
    c = dict(spec)
    u = rms_norm(h, gain, c["eps"])

    def by_head(x, n):  # (seq, n * hd) -> (n, seq, hd)
        return jnp.swapaxes(x.reshape(x.shape[0], n, c["hd"]), 0, 1)

    return tuple(_round(a, operands) for a in (
        by_head(_mm(u, p["wq"], operands), c["heads"]),
        by_head(_mm(u, p["wk"], operands), c["kv"]),
        by_head(_mm(u, p["wv"], operands), c["kv"])))


@functools.partial(jax.jit, static_argnames=("operands",))
def attend(q, k, v, first, *, operands):
    """One head's block of queries (positions ``first``...) against all
    the row's keys: plain softmax attention."""
    s = jnp.dot(q, k.T, precision="highest")
    pos = first + jnp.arange(q.shape[0])
    causal = pos[:, None] >= jnp.arange(k.shape[0])[None, :]
    s = jnp.where(causal, s / math.sqrt(q.shape[-1]), -jnp.inf)
    # softmax(s) v as (e v) / sum(e), e = exp(s - max s): e is the left
    # operand of a matmul, and is rounded as one
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return jnp.dot(_round(e, operands), v, precision="highest") / jnp.sum(
        e, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("operands",))
def project_out(h, att, wo, *, operands):
    return h + _mm(att, wo, operands)


def attention(h, gain, p, *, spec, operands):
    """``h + Attention(RMSNorm(h))`` of one row, a head and a block of
    queries at a time; no rotation, no q/k norm."""
    c = dict(spec)
    q, k, v = attention_project(h, gain, p, spec=spec, operands=operands)
    per = c["heads"] // c["kv"]  # query heads a key head
    heads = []
    for a in range(c["heads"]):
        heads.append(jnp.concatenate([
            attend(q[a, lo:lo + QUERY_BLOCK], k[a // per], v[a // per], lo,
                   operands=operands)
            for lo in range(0, q.shape[1], QUERY_BLOCK)
        ], axis=0))
    return project_out(h, jnp.concatenate(heads, axis=-1), p["wo"], operands=operands)


@functools.partial(jax.jit, static_argnames=("spec", "operands"))
def route(h, gain, f, routing, *, spec, operands):
    """(normed input (n, d), its latent (n, latent), weight of every expert
    on every token (n, E), 0 where not routed, load (rows, E), own top-k
    (rows, seq, top_k))."""
    c = dict(spec)
    rows, seq, d = h.shape
    x = rms_norm(h, gain, c["eps"]).reshape(rows * seq, d)
    s = jax.nn.sigmoid(_mm(x, f["router"]))  # float32 operands, as stated
    _, own = jax.lax.top_k(s + f["bias"].astype(F32), c["top_k"])
    idx = own if routing is None else routing.reshape(rows * seq, c["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, c["num_experts"], dtype=F32), axis=1)  # 0/1
    w = s * chosen
    if c["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    load = jnp.sum(chosen.reshape(rows, seq, -1), axis=1).astype(jnp.int32)
    return (x, _mm(x, f["w_l1"], operands), w * c["scale"], load,
            own.reshape(rows, seq, -1).astype(jnp.int32))


def relu2_ffn(x, w1, w2, mm):
    return mm(jnp.square(jax.nn.relu(mm(x, w1))), w2)


@functools.partial(jax.jit, static_argnames=("operands", "sum_chunk"))
def add_expert(out, x, w1, w2, weight, *, operands, sum_chunk=0):
    """``out + weight * relu(x W1)^2 W2``: one expert on every token,
    masked by its weight (0 on a token not routed to it)."""
    if sum_chunk:
        mm = functools.partial(_mm_bf16_sums, chunk=sum_chunk)
    else:
        mm = functools.partial(_mm, operands=operands)
    return out + weight[:, None] * relu2_ffn(x, w1, w2, mm)


@functools.partial(jax.jit, static_argnames=("operands",))
def leave_latent(h, routed, x, f, *, operands):
    """``h + routed W_l2 + shared(x)``: the held experts' sum out of the
    latent, and the shared expert for every token, whatever is held."""
    mm = functools.partial(_mm, operands=operands)
    out = mm(routed, f["w_l2"]) + relu2_ffn(x, f["shared_w1"], f["shared_w2"], mm)
    return h + out.reshape(h.shape)


def experts(h, gain, f, routing, *, spec, held, operands, sum_chunk):
    """(``h + Experts(RMSNorm(h))``, load, own top-k): every held expert in
    a plain loop over the latent, then out of it, then the shared expert."""
    first, count = held
    x, x_l, w, load, own = route(h, gain, f, routing, spec=spec, operands=operands)
    routed = jnp.zeros_like(x_l)
    for e in range(count):
        routed = add_expert(routed, x_l, f["w1"][e], f["w2"][e], w[:, first + e],
                            operands=operands, sum_chunk=sum_chunk)
    return leave_latent(h, routed, x, f, operands=operands), load, own


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
    top_k) int32) of ``tokens`` (rows, seq), layer by layer: what is on
    the device at one time is one layer's weights and activations."""
    held = tuple(held or (0, int(config["n_routed_experts"])))
    spec = spec_of(config)
    c = dict(spec)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens].astype(F32)
        loads, owns = [], []
        for i, kind in enumerate(c["pattern"]):
            layer = weights["layers"][i]
            gain, p = layer["norm"], layer["mixer"]
            if kind == "M":
                h = jnp.stack([ssm(h[r], gain, p, spec=spec, operands=operands,
                                   sum_chunk=sum_chunk) for r in range(h.shape[0])])
            elif kind == "*":
                h = jnp.stack([attention(h[r], gain, p, spec=spec, operands=operands)
                               for r in range(h.shape[0])])
            else:
                forced = None
                if routing is not None:
                    forced = jnp.asarray(routing, jnp.int32)[:, len(loads)]
                h, load, own = experts(h, gain, p, forced, spec=spec, held=held,
                                       operands=operands, sum_chunk=sum_chunk)
                loads.append(load)
                owns.append(own)
        lp = head(h, weights["final_norm"], weights["head"], tokens,
                  eps=c["eps"], operands=operands)
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
