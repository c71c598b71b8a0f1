"""Per-token log-probabilities of a corpus cut into windows: a language
model's scoring function over a token column, its weights bound to the
verb (run with JAX_PLATFORMS=cpu for a toy size; the benchmark's
`lfm2_score_4k` is this call at LFM2-8B-A1B's published widths)."""

import numpy as np

import tensorframes_tpu as tfs
from tensorframes_tpu.models import lm

config = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, conv_L_cache=3,
    rope_theta=1e6, norm_eps=1e-5, vocab_size=256, num_dense_layers=1, dtype="float32",
    layer_types=["conv", "full_attention", "conv", "conv", "conv"], use_expert_bias=True,
)
params = lm.init_params(config, seed=0)  # a pytree of arrays on the device
windows = np.random.RandomState(0).randint(0, 256, size=(8, 128)).astype(np.int32)
frame = tfs.TensorFrame.from_dict({"tokens": windows}).repartition(2)
# fn(tokens, params) -> {"token_logprob", "expert_load"}; on a TPU leave `interpret` out
fn = lm.scoring_fn(config, interpret=True)
scored = tfs.map_blocks(fn, frame, bindings={"params": params})
scored = tfs.map_blocks(fn, frame, bindings={"params": params})  # no trace, no copy
perplexity = np.exp(-np.asarray(scored["token_logprob"].values)[:, :-1].mean(axis=1))
print("perplexity of each window:", np.round(perplexity, 1))
print(tfs.telemetry.flat_counters()["bindings.bytes_placed"], "bound bytes moved")
