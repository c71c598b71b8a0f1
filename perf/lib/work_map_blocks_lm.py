"""What scoring a row of the `map_blocks_lm` runner needs, from the
configuration's shapes: a row is one window of `score_window` tokens.

Matmul FLOPs only, 2 a multiply-add: experts at the top-k a token is
routed to (not at the experts held), attention at the causal half of the
window, the head over the whole vocabulary. Norms, RoPE, the
convolution's taps, softmax and the sort are not counted.
"""


def _head_dim(c):
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def expert_flops_per_token(c) -> float:
    """The expert matmuls of ONE expert layer: top-k experts, three
    matrices each (gate, up, down)."""
    return c["num_experts_per_tok"] * 3 * 2.0 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_layers(c) -> int:
    return len(c["layer_types"]) - c["num_dense_layers"]


def expert_flops(c, tokens: float) -> float:
    """FLOPs of every expert layer's grouped matmuls for `tokens` tokens:
    what `moe_expert_roofline` divides by the peak."""
    return tokens * expert_layers(c) * expert_flops_per_token(c)


def flops_per_token(c) -> float:
    d, hd, seq = c["hidden_size"], _head_dim(c), c["score_window"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    conv = 2.0 * d * 3 * d + 2.0 * d * d
    attn = (2.0 * d * (heads + 2 * kv) * hd + 2.0 * heads * hd * d
            + 2 * 2.0 * hd * (seq / 2) * heads)  # q k^T and p v, causal half
    dense = 3 * 2.0 * d * c["intermediate_size"]
    moe = expert_flops_per_token(c) + 2.0 * d * c["num_experts"]
    total = 2.0 * d * c["vocab_size"]  # the head
    for i, op in enumerate(c["layer_types"]):
        total += conv if op == "conv" else attn
        total += dense if i < c["num_dense_layers"] else moe
    return total


def work(config: dict) -> dict:
    seq = config["score_window"]
    layers = expert_layers(config)  # log-probabilities, loads, choices: 4 B each
    out_bytes = 4 * seq + 4 * layers * (
        config["num_experts"] + seq * config["num_experts_per_tok"])
    return {
        "bytes_per_row": 4 * seq + out_bytes,
        "flops_per_row": seq * flops_per_token(config),
    }
