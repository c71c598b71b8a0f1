"""What scoring a row of the `map_blocks_lm_latent` runner needs, from the
configuration's shapes under its published key names: a row is one window
of `score_window` tokens.

Matmul FLOPs only, 2 a multiply-add: the attention core (both score parts
and p v) at the causal half of the window, latent attention's five
projections, the routed experts at the top-k a token is routed to (not at
the experts held), the shared expert, the router, the dense FFN, the head
over the whole vocabulary. Norms, RoPE, softmax and the sort are not counted.
"""


def attention_flops_per_token(c) -> float:
    """The attention core of ONE layer at a window of `score_window`: a
    query meets half the window's keys, scores of qk_nope + qk_rope
    products, values of v_head_dim."""
    width = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return 2.0 * width * c["num_attention_heads"] * c["score_window"] / 2


def attention_flops(c, tokens: float) -> float:
    """FLOPs of every layer's attention core for `tokens` tokens: what
    `mla_attention_roofline` divides by the peak."""
    return tokens * c["num_hidden_layers"] * attention_flops_per_token(c)


def projection_flops_per_token(c) -> float:
    d, heads = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return 2.0 * (d * rq + rq * heads * (dn + dr) + d * (rkv + dr)
                  + rkv * heads * (dn + dv) + heads * dv * d)


def expert_flops_per_token(c) -> float:
    """The routed experts' matmuls of ONE expert layer: top-k experts,
    three matrices each (gate, up, down)."""
    return c["num_experts_per_tok"] * 3 * 2.0 * c["hidden_size"] * c["moe_intermediate_size"]


def flops_per_token(c) -> float:
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    shared = 3 * 2.0 * d * c["n_shared_experts"] * c["moe_intermediate_size"]
    moe = expert_flops_per_token(c) + shared + 2.0 * d * c["n_routed_experts"]
    return (layers * (attention_flops_per_token(c) + projection_flops_per_token(c))
            + dense * 3 * 2.0 * d * c["intermediate_size"]
            + (layers - dense) * moe
            + 2.0 * d * c["vocab_size"])  # the head


def work(config: dict) -> dict:
    seq = config["score_window"]
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    # log-probabilities, loads, choices: 4 B each
    out_bytes = 4 * seq + 4 * layers * (
        config["n_routed_experts"] + seq * config["num_experts_per_tok"])
    return {
        "bytes_per_row": 4 * seq + out_bytes,
        "flops_per_row": seq * flops_per_token(config),
    }
