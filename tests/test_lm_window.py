"""`models.lm` with sliding-window and full attention mixed (the family of
``model_type: afmoe``), through the verb path at the small preset of the
benchmark's `trinity-mini` (d = 64, 4 query heads of 16 over 2 key heads, a
window of 16 in a 64-token row, layers sliding, sliding, full, sliding,
sliding, the first dense, 8 experts top-2 beside a shared one, float32,
kernels interpreted), against the plain reference
`tests/references/trinity.py`: each of the family's mechanisms left out of
the program alone is caught, what is not computed raises by its key, the
window's counters, and that the other families trace none of it.
"""

import filecmp
import importlib.util
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tensorframes_tpu as tfs
from perf.lib import lm_weights_window
from perf.runners.map_blocks_lm import model_config
from tensorframes_tpu.models import lm
from tensorframes_tpu.ops import pallas_kernels
from tensorframes_tpu.utils import telemetry as tele

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(HERE, "references", "trinity.py"), "trinity_reference")

with open(os.path.join(ROOT, "perf", "configs", "trinity-mini.json")) as f:
    FILE = json.load(f)
SMALL = model_config(FILE, True)  # the file at its small preset, as the runner runs it
OUTPUTS = ("token_logprob", "expert_load", "expert_choice")
LIMITS = SMALL["limits"]


def _frame(rows=2, seq=64, blocks=2, seed=0):
    toks = np.random.RandomState(seed).randint(0, 256, size=(rows, seq))
    offsets = [int(v) for v in np.linspace(0, rows, blocks + 1)]
    return toks, tfs.TensorFrame(
        [tfs.Column("tokens", jnp.asarray(toks, jnp.int32))], offsets
    )


def _seeded(cfg, seed):
    w = lm_weights_window.weights(cfg, seed)
    return w, lm_weights_window.program_params(cfg, w)


def _score(cfg, frame, params):
    out = lm.score(lm.scoring_fn(cfg, interpret=True), frame, params, cfg)
    return [np.asarray(out[n].values) for n in OUTPUTS]


def test_the_two_copies_of_the_reference_are_one_file():
    assert filecmp.cmp(
        os.path.join(ROOT, "perf", "configs", "trinity-mini.reference.py"),
        os.path.join(HERE, "references", "trinity.py"), shallow=False,
    )


def test_the_reference_imports_nothing_of_the_package():
    with open(os.path.join(HERE, "references", "trinity.py")) as f:
        text = f.read()
    imports = [l for l in text.splitlines() if l.lstrip().startswith(("import ", "from "))]
    assert imports and not [l for l in imports if "tensorframes" in l or "perf" in l]
    for word in ("ragged_dot", "pallas", "lax.sort", "lax.scan", "cumsum"):
        assert word not in text.split('"""', 2)[2]


@pytest.mark.parametrize("seed", [0, 7, 2147483659])
def test_map_blocks_matches_the_reference(seed):
    """Float32 on both sides, the kernel interpreted: the log-probabilities
    part by the order of their float32 sums alone (2e-5: a blockwise
    online softmax against one softmax a query block, a grouped matmul
    against a masked loop over the experts, through five layers); the
    routing is the reference's own, exactly."""
    weights, params = _seeded(SMALL, seed)
    toks, frame = _frame(seed=seed % 1000)
    lp, load, choice = _score(SMALL, frame, params)
    want_lp, want_load, want_choice = (np.asarray(a) for a in ref.forward(SMALL, weights, toks))
    assert lp.dtype == np.float32 and load.dtype == np.int32
    assert load.shape == (2, 4, 8) and choice.shape == (2, 4, 64, 2)
    np.testing.assert_allclose(lp, want_lp, atol=2e-5)
    np.testing.assert_array_equal(load, want_load)
    np.testing.assert_array_equal(np.sort(choice, -1), np.sort(want_choice, -1))
    read = ref.compare((lp, load, choice), (want_lp, want_load, want_choice), 2)
    assert all(read[k] <= LIMITS[k] for k in LIMITS), read
    assert (lp[:, -1] == 0).all() and (lp[:, :-1] < 0).all()


def _without_gate(cfg, params):
    return cfg, {**params, **{k: {n: a for n, a in params[k].items() if n != "w_g"}
                              for k in ("attn", "swa")}}


# each mechanism of the family left out of the PROGRAM alone (the reference
# keeps it): the check at the preset's own limits has to refuse every one
LEFT_OUT = {
    "gate": _without_gate,
    "op_post_norm": lambda cfg, p: (cfg, {k: v for k, v in p.items() if k != "op_post_norm"}),
    "ffn_post_norm": lambda cfg, p: (cfg, {k: v for k, v in p.items() if k != "ffn_post_norm"}),
    "rope_on_full_layers": lambda cfg, p: (dict(cfg, rope=True), p),
    "mup_factor": lambda cfg, p: (dict(cfg, mup_enabled=False), p),
    "window_one_key_wider": lambda cfg, p: (dict(cfg, sliding_window=cfg["sliding_window"] + 1), p),
}


@pytest.mark.parametrize("mechanism", sorted(LEFT_OUT))
def test_a_mechanism_left_out_of_the_program_alone_is_caught(mechanism):
    weights, params = _seeded(SMALL, 5)
    toks, frame = _frame(seed=5)
    cfg, planted = LEFT_OUT[mechanism](SMALL, params)
    got = _score(cfg, frame, planted)
    want = ref.forward(SMALL, weights, toks, routing=got[2])  # along the program's routing
    read = ref.compare(got, want, 2)
    assert read["logprob_p99_abs_err"] > 10 * LIMITS["logprob_p99_abs_err"], read


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 2), ("num_expert_groups", 4), ("num_limited_groups", 2),
    ("score_func", "softmax"), ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("hidden_act", "gelu"), ("tie_word_embeddings", True),
    ("global_attn_every_n_layers", 4), ("sliding_window", None),
    ("layer_types", ["sliding_attention"] * 4 + ["conv"]),
])
def test_what_is_not_computed_raises_by_its_key(key, value):
    cfg = dict(SMALL, **{key: value})
    for call in (lambda: lm.scoring_fn(cfg), lambda: lm.init_params(cfg, 0),
                 lambda: lm.family_keys(cfg)):
        with pytest.raises(ValueError, match=key):
            call()


def test_this_familys_names_give_its_plan():
    keys = lm.family_keys(SMALL)
    assert (keys["n_shared_experts"], keys["norm_topk_prob"], keys["router_score"]) == (
        1, True, "sigmoid")
    assert keys["routed_scaling_factor"] == 2.826 and keys["use_expert_bias"]
    assert not keys["rope"] and keys["attn_gate"] and keys["sandwich_norm"]
    assert keys["norm_eps"] == 1e-5 and lm._shared_width(keys) == 32
    S, F = lm.SLIDING, lm.OPS.index("full_attention")
    np.testing.assert_array_equal(lm.layer_plan(SMALL), [
        [S, 0, 0, 0], [S, 1, 1, 0], [F, 0, 1, 1], [S, 2, 1, 2], [S, 3, 1, 3]])
    # the published list agrees with its period; a cut that starts inside
    # the period gives null for it
    published = ["sliding_attention"] * 3 + ["full_attention"]
    lm.family_keys(dict(SMALL, layer_types=published * 2, num_hidden_layers=8,
                        global_attn_every_n_layers=4))


def test_init_params_and_the_benchmarks_weights_have_one_layout():
    cfg = dict(SMALL, dtype="bfloat16")
    own = lm.init_params(cfg, 0)
    w = lm_weights_window.weights(cfg, 0)
    filled = lm_weights_window.program_params(cfg, w)
    shape = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), t)
    assert shape(own) == shape(filled)
    assert set(own) == {"embed", "head", "final_norm", "op_norm", "ffn_norm", "op_post_norm",
                        "ffn_post_norm", "attn", "swa", "dense", "moe"}
    assert set(own["swa"]) == {"w_qkv", "q_norm", "k_norm", "w_o", "w_g"}
    assert own["swa"]["w_g"].shape == (4, 64, 64) and own["attn"]["w_g"].shape == (1, 64, 64)
    L2, L3 = w["layers"][2], w["layers"][3]
    np.testing.assert_array_equal(filled["attn"]["w_g"][0], L2["op"]["wg"])
    np.testing.assert_array_equal(filled["swa"]["w_qkv"][2][:, 64:96], L3["op"]["wk"])
    np.testing.assert_array_equal(filled["moe"]["shared_up"][2][:, 32:], L3["ffn"]["shared_w3"])
    np.testing.assert_array_equal(filled["moe"]["bias"][2], L3["ffn"]["bias"])
    np.testing.assert_array_equal(filled["ffn_post_norm"][3], L3["ffn_post_norm"])


def test_the_windows_counters():
    """`lm.score` books the band's pairs and the banded grid's block pairs
    (x heads x sliding layers x rows), and `lm.attention_pairs` the full
    layer's causal pairs alone."""
    _, params = _seeded(SMALL, 1)
    _, frame = _frame(seed=1)
    before = dict(tele.flat_counters())
    _score(SMALL, frame, params)
    c = {k: v - before.get(k, 0) for k, v in tele.flat_counters().items()}
    kept = 16 * 17 // 2 + 48 * 16  # Σ_t min(t + 1, 16) over 64 positions
    assert c["lm.swa_pairs"] == 2 * kept * 4 * 4
    # one 64-block of queries and of keys a row (a block is min(1024, seq))
    assert c["lm.swa_blocks"] == 2 * 1 * 4 * 4
    assert c["lm.attention_pairs"] == 2 * (64 * 65 // 2) * 4 * 1
    assert pallas_kernels.band_pairs(32768, 1024, 1024, 2048) == 3 * 32 - 3
    assert pallas_kernels.band_pairs(32768, 512, 512, 2048) == 5 * 64 - 10


def _scopes(cfg, held=None):
    params = jax.eval_shape(lambda: lm.init_params(cfg, 0, held))
    fn = lm.scoring_fn(cfg, held=held, interpret=True)
    text = jax.jit(fn).lower(jnp.zeros((1, 32), jnp.int32), params).as_text(debug_info=True)
    return set(re.findall(r"(lm\.swa|attn\.gate)\b", text))


def _small(name):
    from perf.runners import map_blocks_lm_hybrid, map_blocks_lm_latent

    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        config = json.load(f)
    if name == "joyai-llm-flash":
        return map_blocks_lm_latent.model_config(config, True), None
    if name == "lfm2-8b-a1b":
        return model_config(config, True), None
    return map_blocks_lm_hybrid.model_config(config, True)


@pytest.mark.parametrize("name", ["lfm2-8b-a1b", "joyai-llm-flash",
                                  "nemotron-3-super-120b-a12b", "hy4-preview"])
def test_the_other_families_trace_none_of_it(name):
    cfg, held = _small(name)
    assert _scopes(cfg, held) == set()
    tree = jax.eval_shape(lambda: lm.init_params(cfg, 0, held))
    assert not {"swa", "op_post_norm", "ffn_post_norm"} & set(tree)
    assert "w_g" not in tree.get("attn", {})


def test_this_family_traces_the_band_and_the_gate():
    assert _scopes(SMALL) == {"lm.swa", "attn.gate"}


def test_the_scoring_program_holds_no_64_bit_array():
    cfg = dict(SMALL, dtype="bfloat16")
    params = lm.init_params(cfg, 0)
    text = str(jax.make_jaxpr(lm.scoring_fn(cfg, interpret=True))(
        jnp.zeros((2, 32), jnp.int32), params))
    assert not re.findall(r":[a-z]+64\[\d[^\n]*", text)
