"""What scoring a row of the `map_blocks_lm_hybrid` runner needs, from the
configuration's shapes under its published key names: a row is one window
of `score_window` tokens, and the work is THIS CHIP's share: the routed
experts at the rows expected for the experts held here (`n_routed_experts`
of the router's `router_width`), the head over the slice of the vocabulary
the file holds.

Matmul FLOPs only, 2 a multiply-add: a Mamba-2 layer's in and out
projections and its scan (scores and values at the causal half of a chunk,
the state read and handed on), the attention's projections and its core at
the causal half of the window, the router, the two latent projections, the
shared expert, the held routed experts, the head. Norms, the convolution's
taps, softmax, the gate and the sort are not counted.
"""


def layers(c, kind) -> int:
    return c["hybrid_override_pattern"].count(kind)


def ssd_flops_per_token(c) -> float:
    """The scan of ONE Mamba-2 layer: a group's scores C B^T and a head's
    values at the causal half of a chunk, the carried state read (C S) and
    the state handed on, a head."""
    heads, width = c["mamba_num_heads"], c["mamba_head_dim"]
    groups, state, chunk = c["n_groups"], c["ssm_state_size"], c["chunk_size"]
    return (2.0 * state * (chunk / 2) * groups + 2.0 * width * (chunk / 2) * heads
            + 2 * 2.0 * state * width * heads)


def ssd_flops(c, tokens: float) -> float:
    """FLOPs of every Mamba-2 layer's scan for `tokens` tokens."""
    return tokens * layers(c, "M") * ssd_flops_per_token(c)


def ssd_bytes(c, tokens: float) -> float:
    """Bytes every Mamba-2 layer's scan needs for `tokens` tokens: x in and
    y out (bfloat16), B and C (bfloat16), the time step (float32)."""
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    gn = c["n_groups"] * c["ssm_state_size"]
    return tokens * layers(c, "M") * (2 * 2.0 * inner + 2 * 2.0 * gn + 4.0 * c["mamba_num_heads"])


def ssm_flops_per_token(c) -> float:
    d, inner = c["hidden_size"], c["mamba_num_heads"] * c["mamba_head_dim"]
    wide = 2 * inner + 2 * c["n_groups"] * c["ssm_state_size"] + c["mamba_num_heads"]
    return 2.0 * d * wide + 2.0 * inner * d + ssd_flops_per_token(c)


def expert_flops_per_token(c) -> float:
    """The routed experts' matmuls of ONE expert layer ON THIS CHIP: top-k
    times the held share of the router's experts, two matrices in the
    latent each."""
    rows = c["num_experts_per_tok"] * c["n_routed_experts"] / c["router_width"]
    return rows * 2 * 2.0 * c["moe_latent_size"] * c["moe_intermediate_size"]


def moe_flops_per_token(c) -> float:
    d = c["hidden_size"]
    return (2.0 * d * c["router_width"] + 2 * 2.0 * d * c["moe_latent_size"]
            + 2 * 2.0 * d * c["moe_shared_expert_intermediate_size"]
            + expert_flops_per_token(c))


def attention_flops_per_token(c) -> float:
    d, hd = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return (2.0 * d * (heads + 2 * kv) * hd + 2.0 * heads * hd * d
            + 2 * 2.0 * hd * (c["score_window"] / 2) * heads)  # q k^T and p v


def flops_per_token(c) -> float:
    return (layers(c, "M") * ssm_flops_per_token(c) + layers(c, "E") * moe_flops_per_token(c)
            + layers(c, "*") * attention_flops_per_token(c)
            + 2.0 * c["hidden_size"] * c["vocab_size"])  # the head, over the slice


def work(config: dict) -> dict:
    seq = config["score_window"]
    # log-probabilities, loads (the whole router), choices: 4 B each
    out_bytes = 4 * seq + 4 * layers(config, "E") * (
        config["router_width"] + seq * config["num_experts_per_tok"])
    return {
        "bytes_per_row": 4 * seq + out_bytes,
        "flops_per_row": seq * flops_per_token(config),
    }
