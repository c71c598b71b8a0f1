"""A language model built from a configuration, for scoring a token column.

The configuration is a dict with a published ``config.json``'s keys
(``hidden_size``, ``layer_types``, ``num_dense_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``moe_intermediate_size``, ``num_experts``, ``num_experts_per_tok``,
``conv_L_cache``, ``rope_theta``, ``norm_eps``, ``vocab_size``, the
router's options), under any of three families' names for them
(`family_keys`: ``first_k_dense_replace``, ``n_routed_experts``,
``rms_norm_eps``, ``scoring_func``, ``topk_method``,
``hybrid_override_pattern``, ``mlp_layer_types``, ``rope_parameters``):
there is no class per model. A layer is
``r = h + Op(RMSNorm(h))``,
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
A configuration with ``hybrid_override_pattern`` has layers of ONE normed
mixer, ``h' = h + Op(RMSNorm(h))`` with no FFN half: "M" the Mamba-2 mixer
("ssm": in projection to ``[z | x B C | dt]``, a causal depthwise
convolution and SiLU over ``x B C``, the state-space scan of
`ops.pallas_kernels.ssd_scan`, a gate, a grouped RMSNorm, out projection),
"*" attention (without q/k norms or RoPE in that family), "E" the expert
layer as the layer's operator ("experts": relu² experts of two matrices in
a ``moe_latent_size`` latent the input is projected into and their sum
out of, beside a shared expert of its own width on the residual width).
A configuration whose ``layer_types`` are ``deepseek_sparse_attention``
(`_sparse_keys`: ``indexer_types``, ``mlp_layer_types``,
``rope_parameters``) has gated latent attention on a learned sparse index
in every layer ("sparse_attention", `_sparse_attention_op`): latent
attention's projections; on a ``full`` layer a lightning indexer
(``index_n_heads`` heads of ``index_head_dim``, ReLU scores weighted by
the heads, `ops.pallas_kernels.index_scores`) keeps each query's
``index_topk`` best earlier keys (`_select`), and the ``shared`` layers
after it attend to the same keys, carried by the layer scan; the score of
the kept keys runs on `ops.pallas_kernels.sparse_attention` with a
learned sink logit a head in the softmax's denominator, and the heads'
output is gated elementwise by ``sigmoid(u W_g)`` (``gated_mla``). Its
FFNs are SwiGLUs clamped at ``swiglu_limit``, the experts routed with no
correction bias; its head is float32 (``enable_lm_head_fp32``). With
``enable_ihc`` the residual is ``hc_mult`` float32 streams and each
sublayer F is a manifold-constrained hyper-connection (`_hc_sublayer`):
``X <- M X + a_post ⊗ F(RMSNorm(Σ_i a_pre[i] X[i]))``, the coefficients
from the normed streams, ``M`` a Sinkhorn-normalised mixing; the streams
are read out by ``a_head`` after the last layer. A configuration of
``model_type: afmoe`` (`_afmoe_keys`: ``sliding_window``,
``num_shared_experts``, ``route_norm``, ``route_scale``, ``score_func``)
mixes "sliding_attention" layers, grouped-query attention over the last
``sliding_window`` keys on the kernel's banded grid with RoPE, and
"full_attention" layers without a positional encoding; both gate the
heads' output by ``sigmoid(u W_g)`` before ``W_o``, every sublayer's output
is normed before the residual add (``h + RMSNorm(Op(RMSNorm(h)))``), and
the embedding is scaled by sqrt(d) (``mup_enabled``). A configuration
without these keys traces none of it.

Parameters are one pytree of arrays stacked by kind (every conv part's
``w_in`` in one array, every expert layer's ``w_up`` in one, ...), and all
layers run under ONE `lax.scan` whose step picks the layer's operator and
its FFN with `lax.switch`: compile time does not grow with depth, and each
kernel is in the program, and in a device trace, once. Weights are
bfloat16 (``config["dtype"]``), made on the device from a seed; the
residual stream, norms, softmax, router scores and the log-sum-exp are
float32; matmuls take bfloat16 operands and accumulate in float32. The
head is one kernel, `ops.pallas_kernels.head_logprob`: a tile of tokens'
logits live in VMEM while the vocabulary streams past, and only each
token's log-probability of the next reaches HBM.

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
alone). Under sparse attention a fourth, ``index_choice`` (rows, ``full``
layers, seq, index_topk) int16, the keys each query kept in ascending
order (-1 past min(t + 1, index_topk)), for the same reason. `score` is
that call with its counters.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.pallas_kernels import (
    band_pairs, block_classes, flash_attention, head_logprob, index_scores, index_top_k,
    sparse_attention, ssd_scan,
)
from . import moe

__all__ = [
    "init_params", "scoring_fn", "score", "layer_plan", "held_all", "family_keys",
]

OPS = ("conv", "full_attention", "latent_attention", "ssm", "experts", "sparse_attention",
       "sliding_attention")
ATTENTION = (1, 2)  # the operator kinds that attend to every earlier key
SSM, EXPERTS, SPARSE = OPS.index("ssm"), OPS.index("experts"), OPS.index("sparse_attention")
SLIDING = OPS.index("sliding_attention")
FULL, LATENT = ATTENTION
SWA_BLOCK = 1024  # the sliding window kernel's query and key blocks
NO_FFN = -1  # a layer of one mixer: no FFN half
PATTERN = {"M": "ssm", "*": "full_attention", "E": "experts"}
HEAD_CHUNK = 2048  # tokens whose float32 logits exist at one time (`enable_lm_head_fp32`)
INDEX_QUERIES = 1024  # queries whose index scores exist at one time
SINKHORN = 20  # a hyper-connection's row and column normalisations


def family_keys(config) -> dict:
    """``config`` with this module's names for what either family's
    ``config.json`` says: ``first_k_dense_replace`` is ``num_dense_layers``,
    ``n_routed_experts`` ``num_experts``, ``rms_norm_eps`` ``norm_eps``,
    ``scoring_func`` ``router_score``, ``topk_method: noaux_tc`` a
    per-expert bias in the choice; without ``layer_types`` every one of
    ``num_hidden_layers`` is latent attention. ``hybrid_override_pattern``
    (a character a layer: "M", "*", "E") gives layers of one mixer
    (``layer_types`` "ssm", "full_attention", "experts"; ``one_mixer``),
    attention without q/k norms or RoPE (``qk_norm``, ``rope``), a
    per-expert bias in the choice, ``layer_norm_epsilon`` as ``norm_eps``
    and ``mlp_hidden_act: relu2`` as the FFNs' activation (``ffn_act``).
    What this module does not compute raises, by its key: a
    group-limited expert choice, RoPE length scaling, a "-" layer, a
    bias in a projection, an activation it does not know."""
    c = dict(config)
    hybrid = "hybrid_override_pattern" in c
    if hybrid:
        pattern = str(c["hybrid_override_pattern"])
        unknown = sorted(set(pattern) - set(PATTERN))
        if unknown:
            raise ValueError(
                f"hybrid_override_pattern has {unknown}: layers are 'M', '*' or 'E' "
                "here ('-', a dense FFN as a layer of its own, is not computed)")
        if int(c.get("num_hidden_layers", len(pattern))) != len(pattern):
            raise ValueError(
                f"num_hidden_layers = {c['num_hidden_layers']}: "
                f"hybrid_override_pattern has {len(pattern)} layers")
        c["layer_types"] = [PATTERN[ch] for ch in pattern]
        c["num_dense_layers"] = 0
        for key, value in (("one_mixer", True), ("qk_norm", False), ("rope", False),
                           ("use_expert_bias", True)):
            c.setdefault(key, value)
    if "deepseek_sparse_attention" in c.get("layer_types", ()):
        _sparse_keys(c)
    if c.get("model_type") == "afmoe" or "sliding_attention" in c.get("layer_types", ()):
        _afmoe_keys(c)
    if "layer_types" not in c:
        if "kv_lora_rank" not in c:
            raise ValueError(
                "a configuration gives layer_types, kv_lora_rank or "
                "hybrid_override_pattern")
        c["layer_types"] = ["latent_attention"] * int(c["num_hidden_layers"])
    eps = {k: float(c[k]) for k in ("norm_eps", "rms_norm_eps", "layer_norm_epsilon")
           if k in c}
    if len(set(eps.values())) > 1:
        raise ValueError(f"{eps}: the norms have one epsilon here")
    for ours, theirs in (("num_dense_layers", "first_k_dense_replace"),
                         ("num_experts", "n_routed_experts"),
                         ("norm_eps", "rms_norm_eps"),
                         ("norm_eps", "layer_norm_epsilon"),
                         ("router_score", "scoring_func"),
                         ("router_score", "score_func"),
                         ("n_shared_experts", "num_shared_experts"),
                         ("norm_topk_prob", "route_norm"),
                         ("routed_scaling_factor", "route_scale")):
        if ours not in c and theirs in c:
            c[ours] = c[theirs]
    if "use_expert_bias" not in c and "topk_method" in c:
        c["use_expert_bias"] = c["topk_method"] == "noaux_tc"
    if "ffn_act" not in c and "mlp_hidden_act" in c:
        act = c["mlp_hidden_act"]
        if act not in ("silu", "relu2"):
            raise ValueError(f"mlp_hidden_act = {act!r}: 'silu' (SwiGLU) or 'relu2' here")
        c["ffn_act"] = "relu2" if act == "relu2" else "swiglu"
    for key in ("use_bias", "mlp_bias", "attention_bias", "mamba_proj_bias"):
        if c.get(key):
            raise ValueError(f"{key} = {c[key]!r}: a bias in a projection is not computed here")
    if hybrid:
        for key, want in (("mamba_hidden_act", "silu"), ("use_conv_bias", True),
                          ("sliding_window", None)):
            if c.get(key, want) != want:
                raise ValueError(f"{key} = {c[key]!r}: only {want!r} is computed here")
        inner = int(c["mamba_num_heads"]) * int(c["mamba_head_dim"])
        if "expand" in c and inner != int(c["expand"]) * int(c["hidden_size"]):
            raise ValueError(
                f"expand = {c['expand']}: mamba_num_heads x mamba_head_dim is {inner}")
    for key in ("n_group", "topk_group", "num_expert_groups", "num_limited_groups"):
        if int(c.get(key) or 1) != 1:
            raise ValueError(
                f"{key} = {c[key]}: a group-limited expert choice is not computed here"
            )
    if c.get("rope_scaling") is not None:
        raise ValueError(
            f"rope_scaling = {c['rope_scaling']!r}: RoPE length scaling is not computed here"
        )
    return c


def _afmoe_keys(c):
    """In place: the names of a family of sliding-window and full attention
    mixed (``model_type: afmoe``): ``layer_types`` "sliding_attention"
    (the last ``sliding_window`` keys, RoPE) and "full_attention" (every
    earlier key, no positional encoding), both gated elementwise
    (``attn_gate``) and every sublayer's output normed before the residual
    add (``sandwich_norm``); the expert bias in the choice; the embedding
    times sqrt(d) under ``mup_enabled``. ``layer_types`` governs;
    ``global_attn_every_n_layers``, where given, has to agree with it.
    What is not computed raises by its key."""
    types = list(c["layer_types"])
    if set(types) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types = {sorted(set(types))}: 'sliding_attention' or "
                         "'full_attention' in this family")
    every = c.get("global_attn_every_n_layers")
    if every and [t == "full_attention" for t in types] != [
            (i + 1) % int(every) == 0 for i in range(len(types))]:
        raise ValueError(f"global_attn_every_n_layers = {every}: layer_types has its full "
                         "layers elsewhere")
    if "sliding_attention" in types and not c.get("sliding_window"):
        raise ValueError("sliding_window: a sliding_attention layer needs its window")
    for key, want in (("score_func", "sigmoid"), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False)):
        if c.get(key, want) != want:
            raise ValueError(f"{key} = {c[key]!r}: only {want!r} is computed here")
    for key, value in (("rope", False), ("attn_gate", True), ("sandwich_norm", True),
                       ("use_expert_bias", True)):
        c.setdefault(key, value)


def _sparse_keys(c):
    """In place: the names of a family of gated latent attention on a
    learned sparse index (``layer_types: deepseek_sparse_attention``,
    ``indexer_types`` "full" / "shared", ``mlp_layer_types`` "dense" /
    "sparse", ``rope_parameters``), with hyper-connections
    (``enable_ihc``, ``hc_mult``) and a float32 head where it says so.
    What is not computed raises by its key."""
    types = list(c["layer_types"])
    n = int(c.get("num_hidden_layers", len(types)))
    if set(types) != {"deepseek_sparse_attention"}:
        raise ValueError(f"layer_types = {sorted(set(types))}: every layer is "
                         "deepseek_sparse_attention in this family")
    for key in ("use_dsa", "use_mla"):
        if not c.get(key):
            raise ValueError(f"{key} = {c.get(key)!r}: sparse attention here is on latent attention")
    index = list(c["indexer_types"])
    if set(index) - {"full", "shared"} or index[:1] != ["full"]:
        raise ValueError(f"indexer_types = {index}: 'full' first, then 'full' or 'shared'")
    kinds = list(c.get("mlp_layer_types", ["sparse"] * n))
    dense = kinds.count("dense")
    if kinds != ["dense"] * dense + ["sparse"] * (len(kinds) - dense):
        raise ValueError(f"mlp_layer_types = {kinds}: leading 'dense' layers, then 'sparse'")
    if not len(types) == len(index) == len(kinds) == n:
        raise ValueError(f"num_hidden_layers = {n}: layer_types, indexer_types and "
                         "mlp_layer_types give a kind a layer")
    if c.get("gated_mla") and c.get("gating_type", "elementwise") != "elementwise":
        raise ValueError(f"gating_type = {c['gating_type']!r}: only 'elementwise' is computed here")
    rope = c.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_parameters = {rope!r}: RoPE length scaling is not computed here")
    if "rope_theta" in rope:
        c.setdefault("rope_theta", rope["rope_theta"])
    c["layer_types"] = ["sparse_attention"] * n
    c.setdefault("num_dense_layers", dense)


def held_all(config) -> Tuple[int, int]:
    return (0, int(family_keys(config)["num_experts"]))


def layer_plan(config):
    """Per layer: (operator kind, index in that kind's stack, 1 if the FFN
    is the expert layer, index in that FFN kind's stack), as int32 rows;
    a layer of one mixer has `NO_FFN` for its FFN's kind (its expert
    layers are operators, kind `EXPERTS`)."""
    config = family_keys(config)
    types = list(config["layer_types"])
    dense = int(config["num_dense_layers"])
    seen = {k: 0 for k in OPS}
    rows = []
    for i, t in enumerate(types):
        is_moe = int(i >= dense)
        ffn = (NO_FFN, 0) if config.get("one_mixer") else (is_moe, i - dense if is_moe else i)
        rows.append((OPS.index(t), seen[t]) + ffn)
        seen[t] += 1
    return np.asarray(rows, dtype=np.int32).reshape(len(types), 4)


def _expert_layers(plan):
    """The layers with experts, as operator or as FFN."""
    return np.flatnonzero((plan[:, 0] == EXPERTS) | (plan[:, 2] == 1)).astype(np.int32)


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


def _index_shapes(config) -> Dict[str, Tuple[int, ...]]:
    """One lightning indexer's arrays: ``w_q`` from the query latent to
    ``index_n_heads`` heads of ``index_head_dim``, ``w_k`` one key a token
    for all of them (a LayerNorm over it: ``k_norm``, ``k_bias``), ``w_w``
    the heads' weights."""
    d, heads = int(config["hidden_size"]), int(config["index_n_heads"])
    width = int(config["index_head_dim"])
    return {"w_q": (int(config["q_lora_rank"]), heads * width), "w_k": (d, width),
            "k_norm": (width,), "k_bias": (width,), "w_w": (d, heads)}


def _hc_width(config) -> int:
    """The residual's streams (``hc_mult`` where ``enable_ihc``), else 0."""
    return int(config.get("hc_mult", 1)) if config.get("enable_ihc") else 0


def _full_index(config) -> np.ndarray:
    """A layer's place in the indexers' stack, -1 where it reuses the
    selection of the last ``full`` layer before it."""
    full = np.asarray([t == "full" for t in config["indexer_types"]])
    return np.where(full, np.cumsum(full) - 1, -1).astype(np.int32)


def _shared_width(config) -> int:
    if not int(config.get("n_shared_experts") or 0):
        return 0
    return int(config.get("moe_shared_expert_intermediate_size") or
               config["n_shared_experts"] * config["moe_intermediate_size"])


def _ssm_sizes(config):
    """(heads, head width, groups, state, kernel) of the Mamba-2 mixer."""
    heads, width = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    return (heads, width, int(config["n_groups"]), int(config["ssm_state_size"]),
            int(config["conv_kernel"]))


def _ssm_shapes(config, std) -> Dict[str, Tuple]:
    """One Mamba-2 mixer's arrays and how each is drawn: ``w_in`` holds
    ``[z | x B C | dt]`` side by side, as the family's checkpoints do."""
    d = int(config["hidden_size"])
    heads, width, groups, state, k = _ssm_sizes(config)
    inner, conv = heads * width, heads * width + 2 * groups * state
    return {
        "w_in": ((d, inner + conv + heads), std),
        "conv_w": ((k, conv), float(1.0 / np.sqrt(k))), "conv_b": ((conv,), std),
        "dt_bias": ((heads,), "dt_bias"), "A_log": ((heads,), "A_log"),
        "D": ((heads,), "one"), "norm": ((inner,), None), "w_out": ((inner, d), std),
    }


def init_params(config, seed: int, held: Optional[Tuple[int, int]] = None):
    """The model's parameters on the default device, from ``seed``:
    normal(0, ``initializer_range``) matrices, norm gains near 1, the
    convolution's taps normal(0, 1/sqrt(kernel)), the router bias
    normal(0, ``router_bias_range``), the experts' down projections
    normal(0, ``expert_out_range``) and the latent queries' up projection
    normal(0, ``query_out_range``) where the configuration gives one; of a
    Mamba-2 mixer ``A_log`` the log of uniform(1, 16), ``dt_bias`` the
    inverse softplus of log-uniform(``time_step_min``, ``time_step_max``)
    floored at ``time_step_floor``, ``D`` 1. ``held = (first, count)``
    makes only those experts' weights (the router keeps its full width)."""
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
    n_swa = int(np.sum(plan[:, 0] == SLIDING))
    n_mla = int(np.sum(np.isin(plan[:, 0], (2, SPARSE))))
    n_ssm = int(np.sum(plan[:, 0] == SSM))
    n_moe = len(_expert_layers(plan))
    n_dense = int(np.sum(plan[:, 2] == 0))
    dtype = jnp.dtype(config.get("dtype", "bfloat16"))
    std = float(config.get("initializer_range", 0.02))
    bias_std = float(config.get("router_bias_range", 0.1))
    # matrices side by side in an up projection; the experts' input width
    side = 2 if config.get("ffn_act", "swiglu") == "swiglu" else 1
    latent = int(config.get("moe_latent_size") or 0)
    de = latent or d
    qkv_std = std
    if "query_out_range" in config and not n_mla:  # the queries' columns apart
        qkv_std = ((heads * hd, float(config["query_out_range"])), (2 * kv * hd, std))

    shapes = {
        "embed": ((v, d), std), "head": ((d, v), std),
        "final_norm": ((d,), None),
        "op_norm": ((len(plan), d), None), "ffn_norm": ((len(plan), d), None),
        "conv": {"w_in": ((n_conv, d, 3 * d), std),
                 "taps": ((n_conv, k, d), float(1.0 / np.sqrt(k))),
                 "w_out": ((n_conv, d, d), std)},
        "attn": {"w_qkv": ((n_attn, d, (heads + 2 * kv) * hd), qkv_std),
                 "q_norm": ((n_attn, hd), None), "k_norm": ((n_attn, hd), None),
                 "w_o": ((n_attn, heads * hd, d), std)},
        "dense": {"w_up": ((n_dense, d, side * f), std),
                  "w_down": ((n_dense, f, d), std)},
        "moe": {"router": ((n_moe, d, e), std),
                "bias": ((n_moe, e), bias_std),
                "w_up": ((n_moe, count, de, side * fe), std),
                "w_down": ((n_moe, count, fe, de),
                           float(config.get("expert_out_range", std)))},
    }
    if not config.get("qk_norm", True):
        del shapes["attn"]["q_norm"], shapes["attn"]["k_norm"]
    if config.get("attn_gate"):  # sigmoid(u W_g) on the heads' output
        shapes["attn"]["w_g"] = ((n_attn, d, heads * hd), std)
    if n_swa:  # the sliding layers' own stack of the same arrays
        shapes["swa"] = {name: ((n_swa,) + shape[1:], scale)
                         for name, (shape, scale) in shapes["attn"].items()}
    if config.get("sandwich_norm"):  # each sublayer's output normed
        shapes["op_post_norm"] = ((len(plan), d), None)
        shapes["ffn_post_norm"] = ((len(plan), d), None)
    if latent:
        shapes["moe"]["latent_in"] = ((n_moe, d, latent), std)
        shapes["moe"]["latent_out"] = ((n_moe, latent, d), std)
    if n_mla:
        scale = {"q_norm": None, "kv_norm": None,
                 "w_qb": float(config.get("query_out_range", std))}
        shapes["mla"] = {
            name: ((n_mla,) + shape, scale.get(name, std))
            for name, shape in _latent_shapes(config).items()
        }
    if SPARSE in plan[:, 0]:
        heads, dv = int(config["num_attention_heads"]), int(config["v_head_dim"])
        if config.get("gated_mla"):
            shapes["mla"]["w_g"] = ((n_mla, d, heads * dv), std)
        if config.get("learnable_sink"):
            shapes["mla"]["sink"] = ((n_mla, heads), float(config.get("sink_range", std)))
        n_full = int(np.sum(_full_index(config) >= 0))
        shapes["index"] = {name: ((n_full,) + shape, None if name == "k_norm" else std)
                           for name, shape in _index_shapes(config).items()}
    n_hc = _hc_width(config)
    if n_hc:  # a sublayer's maps: [pre (n) | post (n) | residual (n x n)]
        maps = 2 * n_hc + n_hc * n_hc
        shapes["hc"] = {"phi": ((len(plan), 2, n_hc * d, maps), std),
                        "alpha": ((len(plan), 2, 3), std), "bias": ((len(plan), 2, maps), std)}
        shapes["hc_head"] = {"phi": ((n_hc * d, n_hc), std), "alpha": ((1,), std),
                             "bias": ((n_hc,), std)}
    if n_ssm:
        shapes["ssm"] = {
            name: ((n_ssm,) + shape, scale)
            for name, (shape, scale) in _ssm_shapes(config, std).items()
        }
    if n_mla or n_swa or config.get("one_mixer"):
        for kind, n in (("conv", n_conv), ("attn", n_attn)):
            if not n:  # this family has the stacks of the operators it has
                del shapes[kind]
    if config.get("one_mixer"):  # no FFN half: its norm and the dense FFN go
        del shapes["ffn_norm"], shapes["dense"]
    if not config.get("use_expert_bias"):  # no correction bias in the choice
        del shapes["moe"]["bias"]
    fs = _shared_width(config)
    if fs:
        shapes["moe"]["shared_up"] = ((n_moe, d, side * fs), std)
        shapes["moe"]["shared_down"] = ((n_moe, fs, d), std)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    step = tuple(float(config.get(key, value)) for key, value in (
        ("time_step_min", 0.001), ("time_step_max", 0.1), ("time_step_floor", 1e-4)))

    def make(key, shape, scale):
        if scale is None:  # a norm's gain
            x = 1.0 + 0.05 * jax.random.normal(key, shape, jnp.float32)
        elif isinstance(scale, tuple):  # (columns, scale) side by side
            by_column = np.concatenate([np.full(n, s_, np.float32) for n, s_ in scale])
            x = by_column * jax.random.normal(key, shape, jnp.float32)
        elif scale == "one":
            x = jnp.ones(shape, jnp.float32)
        elif scale == "A_log":
            x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
        elif scale == "dt_bias":
            lo, hi, floor = step
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, float(np.log(lo)), float(np.log(hi))))
            dt = jnp.maximum(dt, jnp.float32(floor))
            x = dt + jnp.log(-jnp.expm1(-dt))  # softplus(x) = dt
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


def _attention_block(op: int, seq: int) -> int:
    """The query and key block of the attention kernel of a layer of kind
    ``op`` over ``seq`` positions (`flash_attention` / `sparse_attention`
    take it as their block; `score` counts the kernels' block pairs at it)."""
    if op == SLIDING:
        return SWA_BLOCK
    if op == SPARSE:
        return min(512, max(8, seq))
    if op == LATENT:
        # 1,024-blocks: at 32,768 positions the kernel's grid steps, not
        # its matmuls, bound 512-blocks (a layer on a v5e: 151 -> 110 ms)
        return min(1024, max(8, seq))
    # 1,024-blocks from 16,384 positions on, as the latent kernel's
    return min(1024 if seq >= 16384 else 512, max(8, seq))


def _attention_op(config, p, u, interpret, window=None):
    """Grouped-query attention; with ``window`` a sliding layer: the last
    ``window`` keys, RoPE whatever ``rope`` says (it speaks of the full
    layers), the kernel under its own scope, ``lm.swa``. Where the layer has
    ``w_g``, the heads' output times ``sigmoid(u W_g)``, elementwise."""
    with jax.named_scope("lm.attention"):
        rows, seq, _ = u.shape
        heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
        hd, eps = _head_dim(config), float(config["norm_eps"])
        qkv = _matmul(u, p["w_qkv"]).reshape(rows, seq, heads + 2 * kv, hd)
        qkv = jnp.swapaxes(qkv, 1, 2)  # (rows, heads + 2 kv, seq, hd)
        q, k, v = qkv[:, :heads], qkv[:, heads:heads + kv], qkv[:, heads + kv:]
        if config.get("qk_norm", True):
            q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
        if window is not None or config.get("rope", True):
            theta = float(config["rope_theta"])
            q, k = _rope(q, theta), _rope(k, theta)
        dtype = p["w_qkv"].dtype
        if window is not None:
            with jax.named_scope("lm.swa"):
                att = flash_attention(
                    q.astype(dtype), k.astype(dtype), v.astype(dtype), causal=True,
                    scale=float(1.0 / np.sqrt(hd)), block_q=SWA_BLOCK, block_k=SWA_BLOCK,
                    interpret=interpret, window=window,
                )
        else:
            block = _attention_block(FULL, seq)
            att = flash_attention(
                q.astype(dtype), k.astype(dtype), v.astype(dtype), causal=True,
                scale=float(1.0 / np.sqrt(hd)), block_q=block, block_k=block,
                interpret=interpret,
            )
        att = jnp.swapaxes(att, 1, 2).reshape(rows, seq, heads * hd)
        if "w_g" in p:
            with jax.named_scope("attn.gate"):
                att = att.astype(jnp.float32) * jax.nn.sigmoid(_matmul(u, p["w_g"]))
        return _matmul(att, p["w_o"])


def _latent_attention_op(config, p, u, interpret):
    """Latent attention: the score's per-head part ``q_n k_n^T`` and its
    rotary part ``q_r k_r^T`` (``k_r`` one vector a token for all heads)
    are the kernel's two score parts; nothing is concatenated or repeated."""
    with jax.named_scope("lm.mla"):
        rows, seq, _ = u.shape
        dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
        dtype = p["w_qa"].dtype
        _, q_n, q_r, kv, k_r = _latent_project(config, p, u)
        block = _attention_block(LATENT, seq)
        att = flash_attention(
            q_n, kv[..., :dn], kv[..., dn:], q2=q_r.astype(dtype), k2=k_r.astype(dtype),
            causal=True, scale=float(1.0 / np.sqrt(dn + dr)),
            block_q=block, block_k=block, interpret=interpret,
        )
        with jax.named_scope("mla.project"):
            att = jnp.swapaxes(att, 1, 2).reshape(rows, seq, -1)
            return _matmul(att, p["w_o"])


def _latent_project(config, p, u):
    """Latent attention's projections: (``c_q``, ``q_n``, ``q_r``, ``[k_n |
    v]``, ``k_r``), by head (rows, heads, seq, width), RoPE applied; ``q_n``
    and ``[k_n | v]`` in the weights' dtype, ``k_r`` one head."""
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
    return c_q, q_n, q_r, kv, k_r


def _select(config, p, u, c_q, interpret):
    """The lightning indexer of a ``full`` layer and its top-k: (selection
    (rows, seq, seq) int8, 1 where key s is in query t's set; the keys
    (rows, seq, k) int32, -1 past t + 1). ``I[t, s] = Σ_j w[t, j]
    ReLU(q[t, j] · k[s] / sqrt(width))``, ``q = c_q W_q`` (heads of
    ``index_head_dim``), ``k = LayerNorm(u W_k)``, RoPE on the first
    ``qk_rope_head_dim`` of both, ``w = u W_w / sqrt(heads)``; query t
    keeps the ``index_topk`` largest over s <= t (every s <= t while t <
    k), ties at the k-th score kept from the lowest key on, the keys in
    ascending order. The scores exist `INDEX_QUERIES` queries at a time:
    the kernel's block, then its selection, `index_top_k` (an exact
    threshold and one compaction, no sort; -0.0 counts as +0.0; a tile of
    queries below k keeps every causal key and selects nothing) and the
    mask by the threshold."""
    rows, seq, _ = u.shape
    heads, width = int(config["index_n_heads"]), int(config["index_head_dim"])
    dr, eps = int(config["qk_rope_head_dim"]), float(config["norm_eps"])
    theta, dtype = float(config["rope_theta"]), p["w_q"].dtype
    top = min(int(config["index_topk"]), seq)
    with jax.named_scope("dsa.project"):
        q = _matmul(c_q, p["w_q"]).reshape(rows, seq, heads, width)
        q = jnp.concatenate([jnp.swapaxes(_rope(jnp.swapaxes(q[..., :dr], 1, 2), theta), 1, 2),
                             q[..., dr:]], axis=-1).astype(dtype)
        k = _matmul(u, p["w_k"])
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = (k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + eps)
             * p["k_norm"].astype(jnp.float32) + p["k_bias"].astype(jnp.float32))
        k = jnp.concatenate([_rope(k[:, None, :, :dr], theta)[:, 0], k[..., dr:]],
                            axis=-1).astype(dtype)
        w = _matmul(u, p["w_w"]) * jnp.float32(1.0 / np.sqrt(heads))
    block = min(INDEX_QUERIES, seq)
    pad = (-seq) % block
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)))
    i32 = jnp.int32

    def one(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
        wb = lax.dynamic_slice_in_dim(w, start, block, axis=1)
        with jax.named_scope("lm.dsa_index"):
            scores = index_scores(qb, k, wb, start, scale=float(1.0 / np.sqrt(width)),
                                  block_k=512, interpret=interpret)
        with jax.named_scope("dsa.top_k"):
            kth, tied, keys = index_top_k(scores, start, k=top, interpret=interpret)
            s_at, at = lax.iota(i32, seq), start + lax.iota(i32, block)[:, None]
            chosen = (scores > kth) | ((scores == kth) & (s_at <= tied))
            return (chosen & (s_at <= at)).astype(jnp.int8), keys

    chosen, keys = lax.map(one, lax.iota(i32, (seq + pad) // block) * i32(block))
    whole = lambda a: jnp.moveaxis(a, 0, 1).reshape((rows, seq + pad) + a.shape[3:])[:, :seq]
    return whole(chosen), whole(keys)


def _index_blocks(config, seq: int) -> Tuple[int, int]:
    """A row's query blocks of the ``full`` layers at a window of ``seq``:
    (chosen by threshold, keeping every causal key: the block lies below
    ``index_topk``), as `_select`'s kernel takes them."""
    block = min(INDEX_QUERIES, seq)
    top = min(int(config["index_topk"]), seq)
    starts = range(0, seq, block)
    full = int(np.sum(_full_index(config) >= 0))
    prefix = sum(start + block <= top for start in starts)
    return full * (len(starts) - prefix), full * prefix


def _sparse_attention_op(config, p, index_p, u, carried, index_at, interpret):
    """Gated latent attention on a learned sparse index, with a sink:
    latent attention's projections (`_latent_project`); on a ``full``
    layer (``index_at`` >= 0) the indexer chooses each query's keys
    (`_select`), a ``shared`` layer takes ``carried``, the selection of the
    ``full`` layer before it; the score of the selected keys on
    `sparse_attention` with a learned sink logit a head (``sink``); the
    heads' output times ``sigmoid(u W_g)``, elementwise (``gated_mla``),
    then ``W_o``. Returns (y, the selection handed on)."""
    rows, seq, _ = u.shape
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dtype = p["w_qa"].dtype
    c_q, q_n, q_r, kv, k_r = _latent_project(config, p, u)
    carried = lax.cond(
        index_at >= 0,
        lambda: _select(config, _at(index_p, jnp.maximum(index_at, 0)), u, c_q, interpret),
        lambda: carried)
    with jax.named_scope("lm.dsa"):
        att = sparse_attention(
            q_n, kv[..., :dn], kv[..., dn:], carried[0], p.get("sink"),
            q2=q_r.astype(dtype), k2=k_r.astype(dtype), scale=float(1.0 / np.sqrt(dn + dr)),
            block=_attention_block(SPARSE, seq), interpret=interpret)
    att = jnp.swapaxes(att, 1, 2).reshape(rows, seq, -1)
    if "w_g" in p:
        with jax.named_scope("mla.gate"):
            att = att.astype(jnp.float32) * jax.nn.sigmoid(_matmul(u, p["w_g"]))
    with jax.named_scope("mla.project"):
        return _matmul(att, p["w_o"]), carried


def _hc_coefficients(config, p, X):
    """A hyper-connection's coefficients from the residual's ``n`` streams
    ``X`` (rows, seq, n, d), float32: ``x = vec(X) / rms(vec(X))``; the
    read-in ``a_pre = sigmoid(alpha_0 x phi_pre + b_pre)``, the write-out
    ``a_post = hc_magnitude * sigmoid(alpha_1 x phi_post + b_post)`` and the
    mixing ``M = Sinkhorn(exp(alpha_2 mat(x phi_res) + b_res))`` (`SINKHORN`
    row then column normalisations, ``hc_eps`` in each denominator),
    ``phi`` side by side in ``p["phi"]``; the maps take float32 operands at
    full precision."""
    n = X.shape[-2]
    eps, f32 = float(config.get("hc_eps", 1e-6)), jnp.float32
    flat = X.reshape(X.shape[:-2] + (-1,))
    x = flat * lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + float(config["norm_eps"]))
    c = jnp.dot(x, p["phi"].astype(f32), precision=lax.Precision.HIGHEST)
    alpha, b = p["alpha"].astype(f32), p["bias"].astype(f32)
    pre = jax.nn.sigmoid(alpha[0] * c[..., :n] + b[:n])
    if c.shape[-1] == n:  # the read-out after the last layer
        return pre
    post = f32(config.get("hc_magnitude", 2.0)) * jax.nn.sigmoid(
        alpha[1] * c[..., n:2 * n] + b[n:2 * n])
    m = jnp.exp(alpha[2] * c[..., 2 * n:] + b[2 * n:]).reshape(c.shape[:-1] + (n, n))
    for _ in range(SINKHORN):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return pre, post, m


def _streams(a, X):
    """``Σ_i a[..., i] X[..., i, :]`` as elementwise float32 sums (a dot
    over four streams would round X to bfloat16 passes on the chip)."""
    return sum(a[..., i, None] * X[..., i, :] for i in range(X.shape[-2]))


def _hc_sublayer(config, p, X, f):
    """``X <- M X + a_post ⊗ f(u)``, ``u = Σ_i a_pre[i] X[i]``
    (`_hc_coefficients`); ``f`` returns its output first and hands on the
    rest."""
    with jax.named_scope("lm.hc"):
        pre, post, m = _hc_coefficients(config, p, X)
        u = _streams(pre, X)
    y, *rest = f(u)
    with jax.named_scope("lm.hc"):
        mixed = jnp.stack([_streams(m[..., i, :], X) for i in range(X.shape[-2])], axis=-2)
        return (mixed + post[..., None] * y[..., None, :],) + tuple(rest)


def _ssm_op(config, p, u, interpret):
    """The Mamba-2 mixer: ``[z | x B C | dt] = u W_in``; a causal depthwise
    convolution, its bias and SiLU over ``x B C``; ``Δ = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)``; the state-space scan (with the ``D``
    skip) on `ssd_scan`; the gate ``y silu(z)``, then an RMSNorm over each
    of the groups' shares of the inner width; ``W_out``."""
    with jax.named_scope("lm.ssm"):
        rows, seq, _ = u.shape
        heads, width, groups, state, k = _ssm_sizes(config)
        inner, conv = heads * width, heads * width + 2 * groups * state
        dtype, f32 = p["w_in"].dtype, jnp.float32
        with jax.named_scope("ssm.project"):
            # the three column blocks apart: each leaves its matmul alone
            z = _matmul(u, p["w_in"][:, :inner])
            xbc = _matmul(u, p["w_in"][:, inner:inner + conv])
            dt = _matmul(u, p["w_in"][:, inner + conv:])
        with jax.named_scope("ssm.conv"):
            # tap j weighs position t - (k - 1 - j): zeros to the left
            padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
            xbc = sum(p["conv_w"][j].astype(f32) * padded[:, j:j + seq] for j in range(k))
            xbc = jax.nn.silu(xbc + p["conv_b"].astype(f32)).astype(dtype)
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
        with jax.named_scope("lm.ssd"):
            y = ssd_scan(
                xbc[..., :inner].reshape(rows, seq, heads, width), dt,
                -jnp.exp(p["A_log"].astype(f32)),
                xbc[..., inner:inner + groups * state].reshape(rows, seq, groups, state),
                xbc[..., inner + groups * state:].reshape(rows, seq, groups, state),
                p["D"].astype(f32), chunk=int(config["chunk_size"]), interpret=interpret,
            )
        g = y.reshape(rows, seq, inner).astype(f32) * jax.nn.silu(z)
        # a group's share of the inner width at a time: slices along the
        # lanes, where a (groups, width) reshape is a relayout copy each way
        # (6.5 ms a layer at 32,768 x 8,192: my chip run, PR 35)
        per, eps = inner // groups, float(config["norm_eps"])
        shares = [g[..., j * per:(j + 1) * per] for j in range(groups)]
        g = jnp.concatenate(
            [s * lax.rsqrt(jnp.mean(s * s, axis=-1, keepdims=True) + eps) for s in shares],
            axis=-1) * p["norm"].astype(f32)
        with jax.named_scope("ssm.project"):
            return _matmul(g, p["w_out"])


def _dense_ffn(p, u, act: str = "swiglu", limit=None):
    """An FFN of two or (SwiGLU) three matrices over the tokens ``u`` (...,
    d), in as many parts as keep the up projection's float32 output and
    the activation within `moe.PART_BYTES` (`moe.parts_for`, one expert a
    token)."""
    d, up = p["w_up"].shape
    f = p["w_down"].shape[0]

    def part(x):
        return _matmul(moe.activation(act, _matmul(x, p["w_up"]), limit), p["w_down"])

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
    act, limit = config.get("ffn_act", "swiglu"), config.get("swiglu_limit")
    flat = u.reshape(rows * seq, d)
    idx, w = moe.route(
        flat, p["router"], p["bias"] if config.get("use_expert_bias") else None,
        top_k=top_k, score=config.get("router_score", "sigmoid"),
        norm_topk=bool(config.get("norm_topk_prob", True)),
        scale=float(config.get("routed_scaling_factor", 1.0)),
    )
    x = flat  # the router reads the input; so do the experts, or a latent of it
    if "latent_in" in p:
        with jax.named_scope("moe.latent"):
            x = _matmul(flat, p["latent_in"])
    y = moe.held_experts(
        x.astype(stacks["w_up"].dtype), idx, w, stacks["w_up"], stacks["w_down"],
        held, act=act, layer=i, experts=e, limit=limit,
    )
    if "latent_out" in p:
        with jax.named_scope("moe.latent"):
            y = _matmul(y, p["latent_out"])
    if "shared_up" in p:  # once for every token, whatever is held here
        with jax.named_scope("moe.shared"):
            y = y + _dense_ffn(
                {"w_up": p["shared_up"], "w_down": p["shared_down"]}, flat, act, limit)
    load = jnp.sum(
        idx.reshape(rows, seq * top_k, 1) == jnp.arange(e, dtype=jnp.int32),
        axis=1, dtype=jnp.int32,
    )
    return y.reshape(rows, seq, d), load, idx.reshape(rows, seq, top_k)


def _head(config, params, h, tokens, interpret):
    """log p(next token) per position: (rows, seq) float32, the last
    position 0. In the weights' dtype, one kernel (`head_logprob`) whose
    logits never leave VMEM; under ``enable_lm_head_fp32`` float32
    operands at full precision, the logits made a chunk of tokens at a
    time."""
    with jax.named_scope("lm.head"):
        rows, seq, d = h.shape
        x = _rms_norm(h, params["final_norm"], float(config["norm_eps"]))
        target = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        n = rows * seq
        if not config.get("enable_lm_head_fp32"):
            w = params["head"]
            lp = head_logprob(x.reshape(n, d).astype(w.dtype), w, target.reshape(n),
                              interpret=interpret)
            return lp.reshape(rows, seq).at[:, -1].set(0.0)
        chunk = min(HEAD_CHUNK, n)
        pad = (-n) % chunk
        x = jnp.pad(x.reshape(n, d), ((0, pad), (0, 0)))
        t = jnp.pad(target.reshape(n), (0, pad))

        def one(args):
            xc, tc = args
            logits = jnp.dot(xc, params["head"].astype(jnp.float32),
                             precision=lax.Precision.HIGHEST)
            lse = jax.nn.logsumexp(logits, axis=-1)
            return moe._along_rows(logits, tc[:, None])[:, 0] - lse

        lp = lax.map(one, (x.reshape(-1, chunk, d), t.reshape(-1, chunk)))
        lp = lp.reshape(-1)[:n].reshape(rows, seq)
        return lp.at[:, -1].set(0.0)


def _pick(branches: Dict, present, which, *args):
    """`lax.switch` over the kinds a model has (``which`` counts among
    ``present``); one kind needs none."""
    if len(present) == 1:
        return branches[present[0]](*args)
    return lax.switch(which, [branches[k] for k in present], *args)


def scoring_fn(
    config, held: Optional[Tuple[int, int]] = None, interpret: bool = False,
) -> Callable:
    """``fn(tokens, params) -> {"token_logprob", "expert_load",
    "expert_choice"}`` (and ``"index_choice"`` under sparse attention) over a
    block of ``(rows, seq)`` token ids (the verb feeds the column named
    as the parameter, ``tokens``). The kernels compile for the
    TPU; ``interpret=True`` (a CPU test, an example) interprets them, and
    nothing chooses that from the backend: a run on the chip is never an
    interpreted one without saying so."""
    config = family_keys(config)
    held = tuple(held or held_all(config))
    plan = layer_plan(config)
    eps = float(config["norm_eps"])
    e, top_k = int(config["num_experts"]), int(config["num_experts_per_tok"])
    act = config.get("ffn_act", "swiglu")
    ops_present = sorted(set(plan[:, 0].tolist()))
    ffn_present = sorted(set(plan[:, 2].tolist()) - {NO_FFN})
    moe_layers = _expert_layers(plan)
    # where a layer's kind stands among the kinds present: the switches' index
    which = np.stack(
        [np.searchsorted(ops_present, plan[:, 0]), plan[:, 1],
         np.searchsorted(ffn_present or [0], plan[:, 2]), plan[:, 3]], axis=1,
    ).astype(np.int32)
    # an expert layer as a layer's operator: every operator then answers
    # with the routing too (none, for the others)
    routed_ops = EXPERTS in ops_present
    limit = config.get("swiglu_limit")
    sparse = SPARSE in ops_present

    def lm_score(tokens, params):
        tokens = tokens.astype(jnp.int32)
        rows, seq = tokens.shape
        with jax.named_scope("lm.embed"):
            h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
            if config.get("mup_enabled"):
                h = h * jnp.float32(np.sqrt(int(config["hidden_size"])))

        def unrouted(y):
            return (y, jnp.zeros((rows, e), jnp.int32),
                    jnp.zeros((rows, seq, top_k), jnp.int32))

        ops = {
            0: lambda u, i: _conv_op(config, _at(params["conv"], i), u),
            1: lambda u, i: _attention_op(config, _at(params["attn"], i), u, bool(interpret)),
            2: lambda u, i: _latent_attention_op(
                config, _at(params["mla"], i), u, bool(interpret)),
            SSM: lambda u, i: _ssm_op(config, _at(params["ssm"], i), u, bool(interpret)),
            SLIDING: lambda u, i: _attention_op(
                config, _at(params["swa"], i), u, bool(interpret),
                window=int(config["sliding_window"])),
        }
        if routed_ops:
            ops = {kind: (lambda u, i, op=op: unrouted(op(u, i))) for kind, op in ops.items()}
            ops[EXPERTS] = lambda u, i: _moe_ffn(config, params["moe"], i, u, held)
        ffns = {
            0: lambda u, i: unrouted(_dense_ffn(_at(params["dense"], i), u, act, limit)),
            1: lambda u, i: _moe_ffn(config, params["moe"], i, u, held),
        }
        if sparse:
            return _sparse_layers(config, params, tokens, h, which, ffns, ffn_present,
                                  moe_layers, interpret)

        def layer(h, xs):
            row, gains, post = xs
            y = _pick(ops, ops_present, row[0], _rms_norm(h, gains[0], eps), row[1])
            if routed_ops:
                y, *routed = y
            if "op_post_norm" in post:
                y = _rms_norm(y, post["op_post_norm"], eps)
            h = h + y
            if ffn_present:  # a layer of one mixer has no FFN half
                y, *routed = _pick(
                    ffns, ffn_present, row[2], _rms_norm(h, gains[1], eps), row[3])
                if "ffn_post_norm" in post:
                    y = _rms_norm(y, post["ffn_post_norm"], eps)
                h = h + y
            return h, tuple(routed)

        gains = (params["op_norm"],) + ((params["ffn_norm"],) if ffn_present else ())
        # sandwich norms: a sublayer's output normed before the residual add
        post = {k: params[k] for k in ("op_post_norm", "ffn_post_norm") if k in params}
        h, (loads, choices) = lax.scan(layer, h, (jnp.asarray(which), gains, post))
        return {
            "token_logprob": _head(config, params, h, tokens, bool(interpret)),
            "expert_load": jnp.swapaxes(loads[moe_layers], 0, 1),
            "expert_choice": jnp.swapaxes(choices[moe_layers], 0, 1),
        }

    return lm_score


def _sparse_layers(config, params, tokens, h, which, ffns, ffn_present, moe_layers,
                   interpret):
    """`scoring_fn`'s layer scan for sparse attention: the scan carries the
    selection (the mask `sparse_attention` reads and the keys, from a
    ``full`` layer to the ``shared`` ones after it) beside the residual,
    which is ``hc_mult`` streams under hyper-connections where
    ``enable_ihc`` (`_hc_sublayer`; the embedding in every stream, read out
    after the last layer by ``a_head``), else one. Outputs as
    `scoring_fn`'s, and ``index_choice`` (rows, full layers, seq,
    index_topk) int16: the keys each query of a ``full`` layer kept."""
    rows, seq = tokens.shape
    eps, n_hc = float(config["norm_eps"]), _hc_width(config)
    index_at = _full_index(config)
    top = min(int(config["index_topk"]), seq)
    gains = (params["op_norm"], params["ffn_norm"])

    def sublayer(X, links, j, f):
        if not n_hc:
            y, *rest = f(X)
            return (X + y,) + tuple(rest)
        return _hc_sublayer(config, jax.tree_util.tree_map(lambda a: a[j], links), X, f)

    def layer(carry, xs):
        X, chosen = carry
        row, (g_op, g_ffn), at, links = xs
        X, chosen = sublayer(X, links, 0, lambda u: _sparse_attention_op(
            config, _at(params["mla"], row[1]), params["index"], _rms_norm(u, g_op, eps),
            chosen, at, bool(interpret)))
        X, load, choice = sublayer(X, links, 1, lambda u: _pick(
            ffns, ffn_present, row[2], _rms_norm(u, g_ffn, eps), row[3]))
        return (X, chosen), (load, choice, chosen[1].astype(jnp.int16))

    if n_hc:
        h = jnp.broadcast_to(h[:, :, None, :], (rows, seq, n_hc, h.shape[-1]))
    start = (jnp.zeros((rows, seq, seq), jnp.int8), jnp.full((rows, seq, top), -1, jnp.int32))
    (X, _), (loads, choices, keys) = lax.scan(
        layer, (h, start),
        (jnp.asarray(which), gains, jnp.asarray(index_at),
         params["hc"] if n_hc else jnp.zeros(len(which), jnp.int32)))
    if n_hc:
        with jax.named_scope("lm.hc"):
            X = _streams(_hc_coefficients(config, params["hc_head"], X), X)
    return {
        "token_logprob": _head(config, params, X, tokens, bool(interpret)),
        "expert_load": jnp.swapaxes(loads[moe_layers], 0, 1),
        "expert_choice": jnp.swapaxes(choices[moe_layers], 0, 1),
        "index_choice": jnp.swapaxes(keys[np.flatnonzero(index_at >= 0).astype(np.int32)], 0, 1),
    }


def score(fn: Callable, frame, params, config, **verb_args):
    """``tfs.map_blocks(fn, frame, bindings={"params": params})`` with the
    model's counters: ``lm.tokens`` (rows x seq of the frame),
    ``moe.routed_rows`` (tokens x experts per token x expert layers),
    ``moe.held_rows_expected`` (the routed rows x the share of the experts
    whose weights ``params`` holds: what this holder's experts are expected
    to compute), ``lm.attention_pairs`` (causal query-key pairs x heads x
    full attention layers), ``lm.ssm_steps`` (tokens x state-space layers);
    under sliding-window attention ``lm.swa_pairs`` (Σ_t min(t + 1,
    sliding_window) x heads x sliding layers x rows) and ``lm.swa_blocks``
    (the (query block, key block) pairs the banded kernel computes, x heads
    x sliding layers x rows: `band_pairs`); ``lm.attention_inner_blocks``
    and ``lm.attention_edge_blocks`` (the (query block, key block) pairs
    the attention kernels compute without and with the positional mask, x
    heads x rows, over the full, sliding, latent and sparse layers:
    `block_classes`);
    under sparse attention ``lm.dsa_selected_pairs`` (Σ_t min(t + 1,
    index_topk) x heads x sparse layers x rows), ``lm.dsa_index_pairs``
    (causal pairs x index heads x ``full`` layers x rows),
    ``lm.index_reuses`` (``shared`` layers x rows),
    ``lm.index_threshold_blocks`` and ``lm.index_prefix_blocks`` (query
    blocks x ``full`` layers x rows chosen by threshold, and those that
    kept every causal key: `_index_blocks`); under hyper-connections
    ``lm.hc_stream_bytes`` (streams x d x 4 B x tokens x 2 sublayers x
    layers); ``lm.head_kernel_tokens`` (the tokens whose log-probability
    the fused head kernel computes: ``lm.tokens``, or 0 under
    ``enable_lm_head_fp32``): all known on the host before the dispatch."""
    from .. import api
    from ..utils import telemetry

    config = family_keys(config)
    seq = int(frame.column("tokens").values.shape[1])
    tokens = frame.nrows * seq
    plan = layer_plan(config)
    routed = tokens * int(config["num_experts_per_tok"]) * len(_expert_layers(plan))
    telemetry.counter_inc("lm.tokens", float(tokens))
    telemetry.counter_inc(
        "lm.head_kernel_tokens", 0.0 if config.get("enable_lm_head_fp32") else float(tokens))
    telemetry.counter_inc("moe.routed_rows", float(routed))
    telemetry.counter_inc(
        "moe.held_rows_expected",
        float(routed * int(params["moe"]["w_up"].shape[1]) / int(config["num_experts"])),
    )
    telemetry.counter_inc(
        "lm.attention_pairs",
        float(frame.nrows * (seq * (seq + 1) // 2)
              * int(config["num_attention_heads"])
              * int(np.isin(plan[:, 0], ATTENTION).sum())),
    )
    telemetry.counter_inc("lm.ssm_steps", float(tokens * int(np.sum(plan[:, 0] == SSM))))
    classes = np.zeros(2)
    for op in (FULL, LATENT, SLIDING, SPARSE):
        layers, block = int(np.sum(plan[:, 0] == op)), _attention_block(op, seq)
        if layers:
            window = int(config["sliding_window"]) if op == SLIDING else None
            classes += layers * np.array(block_classes(seq, block, block, window))
    classes *= frame.nrows * int(config["num_attention_heads"])
    for name, value in zip(("lm.attention_inner_blocks", "lm.attention_edge_blocks"), classes):
        telemetry.counter_inc(name, float(value))
    n_swa = int(np.sum(plan[:, 0] == SLIDING))
    if n_swa:
        window, heads = int(config["sliding_window"]), int(config["num_attention_heads"])
        top = min(window, seq)
        kept = top * (top + 1) // 2 + (seq - top) * top  # Σ_t min(t + 1, window)
        blocks = band_pairs(seq, SWA_BLOCK, SWA_BLOCK, window)
        for name, value in (("lm.swa_pairs", kept), ("lm.swa_blocks", blocks)):
            telemetry.counter_inc(name, float(frame.nrows * value * heads * n_swa))
    if SPARSE in plan[:, 0]:
        full = int(np.sum(_full_index(config) >= 0))
        top = min(int(config["index_topk"]), seq)
        kept = top * (top + 1) // 2 + (seq - top) * top  # Σ_t min(t + 1, top)
        threshold, prefix = _index_blocks(config, seq)
        for name, value in (
                ("lm.dsa_selected_pairs",
                 kept * int(config["num_attention_heads"]) * len(plan)),
                ("lm.dsa_index_pairs", seq * (seq + 1) // 2 * int(config["index_n_heads"]) * full),
                ("lm.index_reuses", len(plan) - full),
                ("lm.index_threshold_blocks", threshold), ("lm.index_prefix_blocks", prefix)):
            telemetry.counter_inc(name, float(frame.nrows * value))
    if _hc_width(config):
        telemetry.counter_inc("lm.hc_stream_bytes", float(
            _hc_width(config) * int(config["hidden_size"]) * 4 * tokens * 2 * len(plan)))
    return api.map_blocks(fn, frame, bindings={"params": params}, **verb_args)
