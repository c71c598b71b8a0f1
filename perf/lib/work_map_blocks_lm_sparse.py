"""What scoring a row of the `map_blocks_lm_sparse` runner needs, from the
configuration's shapes under its published key names: a row is one window
of `score_window` tokens, and the work is THIS CHIP's share: the routed
experts at the rows expected for the experts held here (`n_routed_experts`
of the router's `router_width`), the head over the slice of the vocabulary
the file holds.

Matmul FLOPs only, 2 a multiply-add: latent attention's five projections
and the gate on every layer; the sparse core at the SELECTED pairs (query
t attends to min(t + 1, index_topk) keys: the score's 192 + 64 and the
value's 256 a head), whatever the kernel computes; the indexer of a
``full`` layer (its three projections and the index scores at the causal
pairs, 128 products a head); the hyper-connection maps of the two
sublayers and the read-out; the dense layer's SwiGLU; the router, the
shared expert and the held routed experts; the head. Norms, RoPE, softmax,
the sink, the Sinkhorn, the top-k and the sort are not counted.
"""


def layers(c) -> int:
    return c["num_hidden_layers"]


def full_layers(c) -> int:
    return list(c["indexer_types"]).count("full")


def dense_layers(c) -> int:
    return list(c["mlp_layer_types"]).count("dense")


def selected_per_query(c) -> float:
    """Mean keys a query of the window attends to: Σ_t min(t + 1, topk) / seq."""
    seq, top = c["score_window"], min(c["index_topk"], c["score_window"])
    return (top * (top + 1) / 2 + (seq - top) * top) / seq


def attention_core_flops_per_token(c) -> float:
    """The sparse core of ONE layer: scores of qk_nope + qk_rope and values
    of v_head_dim a head, at the selected keys."""
    width = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return 2.0 * c["num_attention_heads"] * width * selected_per_query(c)


def attention_flops(c, rows: float) -> float:
    """Every layer's sparse core for `rows` windows: what
    `dsa_attention_roofline` divides by the peak."""
    return rows * c["score_window"] * layers(c) * attention_core_flops_per_token(c)


def index_flops(c, rows: float) -> float:
    """Every full layer's index scores for `rows` windows: the causal pairs
    x index heads x 2 x index_head_dim (what `dsa_index_roofline` divides
    by the peak)."""
    seq = c["score_window"]
    return (rows * seq * (seq + 1) / 2 * c["index_n_heads"] * 2.0 * c["index_head_dim"]
            * full_layers(c))


def projection_flops_per_token(c) -> float:
    """Latent attention's five projections and the gate, ONE layer."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return 2.0 * (d * rq + rq * heads * (dn + dr) + d * (rkv + dr)
                  + rkv * heads * (dn + dv) + heads * dv * d + d * heads * dv)


def indexer_projection_flops_per_token(c) -> float:
    ih, ihd = c["index_n_heads"], c["index_head_dim"]
    return 2.0 * (c["q_lora_rank"] * ih * ihd + c["hidden_size"] * (ihd + ih))


def hc_flops_per_token(c) -> float:
    """The maps of ONE layer's two sublayers: n d x (2 n + n^2) each."""
    n = c["hc_mult"]
    return 2 * 2.0 * n * c["hidden_size"] * (2 * n + n * n)


def expert_flops_per_token(c) -> float:
    """The routed experts' matmuls of ONE expert layer ON THIS CHIP: top-k
    times the held share of the router's experts, three matrices each."""
    rows = c["num_experts_per_tok"] * c["n_routed_experts"] / c["router_width"]
    return rows * 3 * 2.0 * c["hidden_size"] * c["moe_intermediate_size"]


def moe_flops_per_token(c) -> float:
    d = c["hidden_size"]
    shared = 3 * 2.0 * d * c["n_shared_experts"] * c["moe_intermediate_size"]
    return 2.0 * d * c["router_width"] + shared + expert_flops_per_token(c)


def flops_per_token(c) -> float:
    d, n = c["hidden_size"], c["hc_mult"]
    seq = c["score_window"]
    index = (indexer_projection_flops_per_token(c)
             + index_flops(c, 1) / seq / full_layers(c))
    return (layers(c) * (projection_flops_per_token(c) + attention_core_flops_per_token(c)
                         + hc_flops_per_token(c))
            + full_layers(c) * index
            + dense_layers(c) * 3 * 2.0 * d * c["intermediate_size"]
            + (layers(c) - dense_layers(c)) * moe_flops_per_token(c)
            + 2.0 * n * d * n  # the read-out's map
            + 2.0 * d * c["vocab_size"])  # the head, over the slice


def work(config: dict) -> dict:
    seq = config["score_window"]
    moe = layers(config) - dense_layers(config)
    # log-probabilities, loads (the whole router), choices: 4 B each; the
    # kept keys: 2 B each
    out_bytes = 4 * seq + 4 * moe * (config["router_width"] + seq * config["num_experts_per_tok"])
    out_bytes += 2 * full_layers(config) * seq * config["index_topk"]
    return {
        "bytes_per_row": 4 * seq + out_bytes,
        "flops_per_row": seq * flops_per_token(config),
    }
