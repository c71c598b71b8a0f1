"""`models.lm` with latent attention (MLA), a shared expert and many small
experts, through the verb path at the small preset of the benchmark's
`joyai-llm-flash` (d = 64, 4 heads, q latent 48, kv latent 32, 16 | 8 | 16,
16 experts top-4 beside a shared one, a dense layer and two expert layers,
vocabulary 256, float32), against the plain reference
`tests/references/joyai.py`; and what the expert layer promises of its
grouping and of its parts.
"""

import filecmp
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tensorframes_tpu as tfs
from perf.lib import lm_weights_latent
from tensorframes_tpu.models import lm, moe
from tensorframes_tpu.runtime.executor import Executor
from tensorframes_tpu.utils import telemetry as tele

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(HERE, "references", "joyai.py"), "joyai_reference")

with open(os.path.join(ROOT, "perf", "configs", "joyai-llm-flash.json")) as f:
    FILE = json.load(f)
# the file's published keys at its small preset, as the benchmark's runner
# hands them over (without the keys `derived` lists)
SMALL = {**{k: v for k, v in FILE.items() if k not in FILE["derived"]},
         **FILE["presets"]["small"]}
OUTPUTS = ("token_logprob", "expert_load", "expert_choice")


def _frame(rows=2, seq=64, blocks=2, seed=0):
    toks = np.random.RandomState(seed).randint(0, 256, size=(rows, seq))
    offsets = [int(v) for v in np.linspace(0, rows, blocks + 1)]
    return toks, tfs.TensorFrame(
        [tfs.Column("tokens", jnp.asarray(toks, jnp.int32))], offsets
    )


def _seeded(cfg, seed):
    w = lm_weights_latent.weights(cfg, seed)
    return w, lm_weights_latent.program_params(cfg, w)


def _score(cfg, frame, params):
    return lm.score(lm.scoring_fn(cfg, interpret=True), frame, params, cfg)


def test_the_two_copies_of_the_reference_are_one_file():
    assert filecmp.cmp(
        os.path.join(ROOT, "perf", "configs", "joyai-llm-flash.reference.py"),
        os.path.join(HERE, "references", "joyai.py"), shallow=False,
    )


def test_the_reference_imports_nothing_of_the_package():
    with open(os.path.join(HERE, "references", "joyai.py")) as f:
        text = f.read()
    imports = [l for l in text.splitlines() if l.startswith(("import ", "from "))]
    assert imports and not [l for l in imports if "tensorframes" in l or "perf" in l]
    for word in ("ragged_dot", "pallas", "lax.sort", "argsort", "lax.scan"):
        assert word not in text.split('"""', 2)[2]


@pytest.mark.parametrize("seed", [0, 7])
def test_map_blocks_matches_the_reference(seed):
    weights, params = _seeded(SMALL, seed)
    toks, frame = _frame(seed=seed)
    out = _score(SMALL, frame, params)
    want_lp, want_load, want_choice = ref.forward(SMALL, weights, toks)
    got_lp, got_load, got_choice = (np.asarray(out[n].values) for n in OUTPUTS)
    assert got_lp.dtype == np.float32 and got_load.dtype == np.int32
    assert got_load.shape == (2, 2, 16) and got_choice.shape == (2, 2, 64, 4)
    np.testing.assert_allclose(got_lp, np.asarray(want_lp), atol=2e-5)
    np.testing.assert_array_equal(got_load, np.asarray(want_load))
    np.testing.assert_array_equal(
        np.sort(got_choice, -1), np.sort(np.asarray(want_choice), -1))
    counts = (got_choice[..., None] == np.arange(16)).sum(axis=(2, 3))
    np.testing.assert_array_equal(counts, got_load)
    assert (got_lp[:, -1] == 0).all() and (got_lp[:, :-1] < 0).all()


def test_the_rotary_key_and_the_shared_expert_are_in_the_result():
    """Taking either out of the program's weights moves the result: the
    comparison above would see a fault in them."""
    weights, params = _seeded(SMALL, 3)
    toks, frame = _frame(seed=3)
    sound = np.asarray(_score(SMALL, frame, params)["token_logprob"].values)
    rkv = SMALL["kv_lora_rank"]
    no_rope = {**params, "mla": {**params["mla"], "w_kva":
               params["mla"]["w_kva"].at[:, :, rkv:].set(0.0)}}
    no_shared = {**params, "moe": {**params["moe"], "shared_down":
                 jnp.zeros_like(params["moe"]["shared_down"])}}
    for broken in (no_rope, no_shared):
        got = np.asarray(_score(SMALL, frame, broken)["token_logprob"].values)
        assert np.abs(got - sound)[:, :-1].max() > 1e-2


@pytest.mark.parametrize("interleave", [True, False])
def test_rope_is_the_references(interleave):
    x = jnp.asarray(np.random.RandomState(1).randn(2, 3, 40, 8), jnp.float32)
    got = lm._rope(x, 32e6, interleave)
    np.testing.assert_allclose(got, ref.rope(x, 32e6, interleave), atol=1e-6)
    # a rotation: norms of the pairs stay, position 0 stays
    np.testing.assert_allclose(
        jnp.sum(got * got, -1), jnp.sum(x * x, -1), rtol=1e-5)
    np.testing.assert_array_equal(got[..., 0, :], x[..., 0, :])
    other = np.asarray(lm._rope(x, 32e6, not interleave))
    assert np.abs(other - np.asarray(got)).max() > 0.1


def _expert_layer(seed=0, rows=48):
    rng = np.random.RandomState(seed)
    d, f, e = 16, 24, 16
    p = {
        "router": jnp.asarray(rng.randn(d, e), jnp.float32),
        "bias": jnp.asarray(0.1 * rng.randn(e), jnp.float32),
        "w1": jnp.asarray(0.3 * rng.randn(e, d, f), jnp.float32),
        "w3": jnp.asarray(0.3 * rng.randn(e, d, f), jnp.float32),
        "w2": jnp.asarray(0.3 * rng.randn(e, f, d), jnp.float32),
        "shared_w1": jnp.asarray(0.3 * rng.randn(d, f), jnp.float32),
        "shared_w3": jnp.asarray(0.3 * rng.randn(d, f), jnp.float32),
        "shared_w2": jnp.asarray(0.3 * rng.randn(f, d), jnp.float32),
    }
    return p, jnp.asarray(rng.randn(1, rows, d), jnp.float32)


SPEC = (("eps", 1e-6), ("top_k", 4), ("num_experts", 16), ("use_bias", True),
        ("norm_topk", True), ("scale", 2.5))


def test_four_shares_of_four_experts_and_the_shared_expert_once_add_up():
    """The guide's share test: each share routes over all 16 experts and
    computes its own 4; what every chip computes alike, the shared
    expert, is counted once."""
    p, r = _expert_layer()
    gain = jnp.ones((16,), jnp.float32)
    whole, load, _ = ref.moe_ffn(r, gain, p, None, spec=SPEC, held=(0, 16),
                                 operands="float32", sum_chunk=0)
    x = ref.rms_norm(r, gain, 1e-6)[0]
    idx, w = moe.route(x, p["router"], p["bias"], top_k=4, scale=2.5)
    w_up = jnp.concatenate([p["w1"], p["w3"]], axis=-1)  # the program's storage
    parts = [
        moe.held_experts(x, idx, w, w_up[first:first + 4], p["w2"][first:first + 4],
                         (first, 4))
        for first in range(0, 16, 4)
    ]
    shared = lm._dense_ffn(
        {"w_up": jnp.concatenate([p["shared_w1"], p["shared_w3"]], axis=-1),
         "w_down": p["shared_w2"]}, x)
    np.testing.assert_allclose(
        r[0] + sum(parts) + shared, np.asarray(whole[0]), atol=2e-5)
    assert int(np.sum(load)) == 4 * r.shape[1]
    assert not np.allclose(parts[0], parts[1])
    # a share alone is what the reference gives for that share (its own
    # experts and, there, the shared expert)
    for first, part in zip(range(0, 16, 4), parts):
        share = {**p, **{n: p[n][first:first + 4] for n in ("w1", "w3", "w2")}}
        want, _, _ = ref.moe_ffn(r, gain, share, None, spec=SPEC, held=(first, 4),
                                 operands="float32", sum_chunk=0)
        np.testing.assert_allclose(r[0] + part + shared, np.asarray(want[0]), atol=2e-5)


def test_every_token_gets_exactly_eight_distinct_experts_one_of_them_forced():
    cfg = dict(SMALL, num_experts_per_tok=8)
    weights, forced = lm_weights_latent.weights(cfg, 1), 11
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        layer = weights["layers"][i]
        layer = {k: layer[k] for k in layer.keys()}
        if i >= cfg["first_k_dense_replace"]:
            layer["ffn"]["bias"] = layer["ffn"]["bias"].at[forced].set(10.0)
        layers.append(layer)
    weights = {**{k: weights[k] for k in weights.keys()}, "layers": layers}
    params = lm_weights_latent.program_params(cfg, weights)
    toks, frame = _frame(rows=2, seq=48, blocks=1)
    out = _score(cfg, frame, params)
    load = np.asarray(out["expert_load"].values)
    choice = np.sort(np.asarray(out["expert_choice"].values), -1)
    assert choice.shape[-1] == 8 and (np.diff(choice, axis=-1) > 0).all()
    assert (load.sum(-1) == 48 * 8).all()  # none dropped, none doubled
    assert (load[..., forced] == 48).all()  # the forced expert, every token
    assert (load <= 48).all()  # an expert at most once a token
    want_lp, want_load, _ = ref.forward(cfg, weights, toks)
    np.testing.assert_array_equal(load, np.asarray(want_load))
    np.testing.assert_allclose(
        np.asarray(out["token_logprob"].values), np.asarray(want_lp), atol=2e-5)


def _counting_sort(key, count):
    """The grouping `held_experts` had before: a one-hot of rows x (count +
    1), a routed row's place its expert's offset plus its rank there."""
    i32 = jnp.int32
    hot = (key[:, None] == jnp.arange(count + 1, dtype=i32)).astype(i32)
    sizes = jnp.sum(hot, axis=0, dtype=i32)
    rank = jnp.cumsum(hot, axis=0, dtype=i32) - hot
    offsets = jnp.cumsum(sizes, dtype=i32) - sizes
    back = jnp.sum(hot * (rank + offsets), axis=1, dtype=i32)
    order = jnp.zeros_like(back).at[back].set(
        jnp.arange(back.shape[0], dtype=i32), unique_indices=True)
    return order, back, sizes[:count]


@pytest.mark.parametrize("count,rows", [(32, 4096), (256, 8192), (256, 100), (4, 64)])
def test_grouping_is_the_counting_sorts_order(count, rows):
    # keys 0..count (count: held elsewhere), some experts empty
    key = np.random.RandomState(count + rows).randint(0, count + 1, size=rows)
    key[key == 3] = 0
    key = jnp.asarray(key, jnp.int32)
    got, want = moe.group_rows(key, count), _counting_sort(key, count)
    for g, w in zip(got, want):
        assert g.dtype == jnp.int32
        np.testing.assert_array_equal(g, w)
    assert int(got[2][3]) == 0
    np.testing.assert_array_equal(np.asarray(key)[np.asarray(got[0])],
                                  np.sort(np.asarray(key)))


def test_an_expert_layer_in_parts_is_the_layer(monkeypatch):
    """A part groups and multiplies its own tokens: the layer in eight
    parts gives what it gives in one, and the stacked weights of several
    layers give what a layer's own slice gives."""
    p, r = _expert_layer(seed=2, rows=64)
    x = r[0]
    idx, w = moe.route(x, p["router"], p["bias"], top_k=4, scale=2.5)
    w_up = jnp.concatenate([p["w1"], p["w3"]], axis=-1)
    one = moe.held_experts(x, idx, w, w_up, p["w2"], (0, 16))
    assert moe.parts_for(64, 4, 16, 48, 24, 4) == 1
    row = max(16 * 4 + 4 * 48 + 24 * 4, 24 * 4 + 8 * 16)
    monkeypatch.setattr(moe, "PART_BYTES", 8 * 4 * row)  # eight tokens' routed rows
    assert moe.parts_for(64, 4, 16, 48, 24, 4) == 8
    assert moe.parts_for(60, 4, 16, 48, 24, 4) == 10  # the fewest that divide
    np.testing.assert_allclose(
        moe.held_experts(x, idx, w, w_up, p["w2"], (0, 16)), one, atol=1e-6)
    stack = lambda a: jnp.stack([jnp.zeros_like(a), a * 0 + 7.0, a])
    np.testing.assert_allclose(
        moe.held_experts(x, idx, w, stack(w_up), stack(p["w2"]), (0, 16),
                         layer=jnp.int32(2)), one, atol=1e-6)


def test_part_sizes_of_the_two_configurations_in_the_benchmark():
    """From the routed rows and the experts' widths alone: 32,768 tokens
    top-4 at width 1,792 stay one part, 32,768 tokens top-8 at width 768
    are taken in parts; every part holds whole tokens."""
    assert moe.parts_for(32768, 4, 2048, 2 * 1792, 1792, 2) == 1
    n = moe.parts_for(32768, 8, 2048, 2 * 768, 768, 2)
    assert n > 1 and 32768 % n == 0
    assert 32768 // n * 8 * max(2 * 2048 + 8 * 768 + 2 * 768,
                                2 * 768 + 8 * 2048) <= moe.PART_BYTES


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 2),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
])
def test_what_is_not_computed_raises_by_its_key(key, value):
    cfg = dict(SMALL, **{key: value})
    for call in (lambda: lm.scoring_fn(cfg), lambda: lm.init_params(cfg, 0),
                 lambda: lm.layer_plan(cfg)):
        with pytest.raises(ValueError, match=key):
            call()
    with pytest.raises(ValueError):
        ref.spec_of(cfg)


def test_either_familys_names_give_one_plan():
    keys = lm.family_keys(SMALL)
    assert keys["layer_types"] == ["latent_attention"] * 3
    assert (keys["num_dense_layers"], keys["num_experts"]) == (1, 16)
    assert keys["norm_eps"] == 1e-6 and keys["use_expert_bias"] is True
    assert keys["router_score"] == "sigmoid"
    plan = lm.layer_plan(SMALL)
    np.testing.assert_array_equal(plan, [[2, 0, 0, 0], [2, 1, 1, 0], [2, 2, 1, 1]])
    # the other family's names pass through as they are
    other = {"layer_types": ["conv", "full_attention"], "num_dense_layers": 1,
             "num_experts": 8, "norm_eps": 1e-5}
    assert lm.family_keys(other) == other
    with pytest.raises(ValueError, match="layer_types"):
        lm.family_keys({"num_hidden_layers": 2})


def test_init_params_and_the_benchmarks_weights_have_one_layout():
    cfg = dict(SMALL, dtype="bfloat16")
    own = lm.init_params(cfg, 0)
    w = lm_weights_latent.weights(cfg, 0)
    filled = lm_weights_latent.program_params(cfg, w)
    shape = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    assert shape(own) == shape(filled)
    assert set(own) == {"embed", "head", "final_norm", "op_norm", "ffn_norm",
                        "mla", "dense", "moe"}  # the stacks of the kinds it has
    layer = w["layers"][2]
    op, ffn = layer["op"], layer["ffn"]
    np.testing.assert_array_equal(filled["mla"]["w_qb"][2], op["w_qb"])
    np.testing.assert_array_equal(filled["mla"]["kv_norm"][2], op["kv_a_norm"])
    np.testing.assert_array_equal(filled["moe"]["w_up"][1][:, :, 32:], ffn["w3"])
    np.testing.assert_array_equal(filled["moe"]["shared_up"][1][:, :32], ffn["shared_w1"])
    np.testing.assert_array_equal(filled["moe"]["shared_down"][1], ffn["shared_w2"])
    np.testing.assert_array_equal(filled["op_norm"][2], layer["op_norm"])


def test_the_scoring_program_holds_no_64_bit_array():
    import re

    cfg = dict(SMALL, dtype="bfloat16")
    params = lm.init_params(cfg, 0)
    text = str(jax.make_jaxpr(lm.scoring_fn(cfg, interpret=True))(
        jnp.zeros((2, 32), jnp.int32), params))
    assert not re.findall(r":[a-z]+64\[\d[^\n]*", text)


def test_bfloat16_weights_stay_near_the_reference():
    cfg = dict(SMALL, dtype="bfloat16", initializer_range=0.02,
               expert_out_range=0.07, query_out_range=0.1)
    weights, params = _seeded(cfg, 3)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree_util.tree_leaves(params))
    toks, frame = _frame()
    out = _score(cfg, frame, params)
    got = [out[n].values for n in OUTPUTS]
    same = ref.compare(got, ref.forward(
        cfg, weights, toks, routing=got[2], operands="bfloat16"), 4)
    f32 = ref.compare(got, ref.forward(cfg, weights, toks, routing=got[2]), 4)
    assert same["logprob_p99_abs_err"] < f32["logprob_p99_abs_err"] < 0.02
    assert same["expert_load_l1_share"] == f32["expert_load_l1_share"] == 0
    assert same["routing_swapped_share"] <= f32["routing_swapped_share"] < 0.1


class _Counting:
    """A scoring function that counts its traces."""

    def __init__(self, cfg):
        self.inner, self.traces = lm.scoring_fn(cfg, interpret=True), 0

    def __call__(self, tokens, params):
        self.traces += 1
        return self.inner(tokens, params)


def test_second_call_traces_nothing_and_moves_no_bound_byte():
    params = lm.init_params(SMALL, 0)
    _, frame = _frame(rows=2, seq=16, blocks=1)
    fn, ex = _Counting(SMALL), Executor()
    first = lm.score(fn, frame, params, SMALL, executor=ex)
    traced = fn.traces
    assert traced >= 1 and ex.cache_misses == 1
    assert tele.flat_counters()["bindings.bytes_placed"] == 0  # where they live
    second = lm.score(fn, frame, params, SMALL, executor=ex)
    assert fn.traces == traced and ex.cache_misses == 1 and ex.cache_hits == 1
    counters = tele.flat_counters()
    assert counters["bindings.bytes_placed"] == 0
    assert counters["lm.tokens"] == 2 * 2 * 16
    assert counters["moe.routed_rows"] == 2 * 2 * 16 * 4 * 2
    assert counters["lm.attention_pairs"] == 2 * 2 * (16 * 17 // 2) * 4 * 3
    np.testing.assert_array_equal(
        np.asarray(first["token_logprob"].values),
        np.asarray(second["token_logprob"].values),
    )
