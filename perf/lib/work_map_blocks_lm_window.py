"""What scoring a row of the `map_blocks_lm_window` runner needs, from the
configuration's shapes under its published key names: a row is one window
of `score_window` tokens, every expert held here.

Matmul FLOPs only, 2 a multiply-add: each layer's q, k, v, gate and output
projections; the attention core (``q k^T`` and ``p v``, 2 x head_dim a
head) of a full layer at the causal half of the window, of a sliding layer
at its band (query t sees min(t + 1, sliding_window) keys), whatever the
kernel computes; the dense layer's SwiGLU; an expert layer's router, its
top-k experts and the shared expert; the head over the whole vocabulary.
Norms, RoPE, softmax, the gate's sigmoid and the sort are not counted.
"""


def _head_dim(c) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def kinds(c, kind) -> int:
    return list(c["layer_types"]).count(kind)


def window_keys(c) -> float:
    """Σ_t min(t + 1, sliding_window) over the window's positions."""
    seq, top = c["score_window"], min(c["sliding_window"], c["score_window"])
    return top * (top + 1) / 2 + (seq - top) * top


def core_flops(c, keys: float) -> float:
    """One layer's attention core over `keys` query-key pairs."""
    return keys * c["num_attention_heads"] * 2 * 2.0 * _head_dim(c)


def window_flops(c, rows: float) -> float:
    """Every sliding layer's core for `rows` windows, at the band: what
    `swa_attention_roofline` divides by the peak."""
    return rows * core_flops(c, window_keys(c)) * kinds(c, "sliding_attention")


def projection_flops_per_token(c) -> float:
    """q, k, v, the gate and the output projection of ONE layer."""
    d, hd = c["hidden_size"], _head_dim(c)
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2.0 * d * hd * (3 * heads + 2 * kv)


def moe_flops_per_token(c) -> float:
    """ONE expert layer: the router, the top-k experts and the shared
    expert, three matrices each."""
    d, fe = c["hidden_size"], c["moe_intermediate_size"]
    shared = c.get("num_shared_experts", 0)
    return 2.0 * d * c["num_experts"] + (c["num_experts_per_tok"] + shared) * 3 * 2.0 * d * fe


def flops_per_token(c) -> float:
    seq, dense = c["score_window"], c["num_dense_layers"]
    full = core_flops(c, seq * (seq + 1) / 2) / seq
    return (len(c["layer_types"]) * projection_flops_per_token(c)
            + kinds(c, "full_attention") * full
            + window_flops(c, 1) / seq
            + dense * 3 * 2.0 * c["hidden_size"] * c["intermediate_size"]
            + (len(c["layer_types"]) - dense) * moe_flops_per_token(c)
            + 2.0 * c["hidden_size"] * c["vocab_size"])  # the head


def work(config: dict) -> dict:
    seq = config["score_window"]
    layers = len(config["layer_types"]) - config["num_dense_layers"]
    # log-probabilities, loads, choices: 4 B each
    out_bytes = 4 * seq + 4 * layers * (
        config["num_experts"] + seq * config["num_experts_per_tok"])
    return {
        "bytes_per_row": 4 * seq + out_bytes,
        "flops_per_row": seq * flops_per_token(config),
    }
