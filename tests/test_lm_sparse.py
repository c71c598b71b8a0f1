"""`models.lm` with gated latent attention on a learned sparse index, a
sink, four-stream hyper-connections and a held share of the experts,
through the verb path at the small preset of the benchmark's `hy4-preview`
(d = 64, 4 heads of 16 + 8 (values 16), 2 index heads of 16, top-16 of a
64-token window, 4 streams, 16 experts top-4 of which 8 held, 5 layers
with indexers full, full, shared, shared, shared, float32, kernels
interpreted), against the plain reference `tests/references/hy4.py`; the
two kernels against numpy; the mixing's Sinkhorn; the shares of the
expert layer; and that the other families trace none of it.
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
from perf.lib import lm_weights_sparse
from perf.runners.map_blocks_lm_hybrid import model_config
from tensorframes_tpu.models import lm, moe
from tensorframes_tpu.ops.pallas_kernels import index_scores, sparse_attention
from tensorframes_tpu.utils import telemetry as tele

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(HERE, "references", "hy4.py"), "hy4_reference")

with open(os.path.join(ROOT, "perf", "configs", "hy4-preview.json")) as f:
    FILE = json.load(f)
# the file at its small preset as the benchmark's runner hands it over: the
# router's width under the published key, and the share held here
SMALL, HELD = model_config(FILE, True)
OUTPUTS = ("token_logprob", "expert_load", "expert_choice", "index_choice")


def _frame(rows=2, seq=64, blocks=2, seed=0):
    toks = np.random.RandomState(seed).randint(0, 256, size=(rows, seq))
    offsets = [int(v) for v in np.linspace(0, rows, blocks + 1)]
    return toks, tfs.TensorFrame(
        [tfs.Column("tokens", jnp.asarray(toks, jnp.int32))], offsets
    )


def _seeded(cfg, seed, held=HELD):
    w = lm_weights_sparse.weights(cfg, seed, held)
    return w, lm_weights_sparse.program_params(cfg, w)


def _score(cfg, frame, params, held=HELD):
    out = lm.score(lm.scoring_fn(cfg, held=held, interpret=True), frame, params, cfg)
    return [np.asarray(out[n].values) for n in OUTPUTS]


def test_the_two_copies_of_the_reference_are_one_file():
    assert filecmp.cmp(
        os.path.join(ROOT, "perf", "configs", "hy4-preview.reference.py"),
        os.path.join(HERE, "references", "hy4.py"), shallow=False,
    )


def test_the_reference_imports_nothing_of_the_package():
    with open(os.path.join(HERE, "references", "hy4.py")) as f:
        text = f.read()
    imports = [l for l in text.splitlines() if l.startswith(("import ", "from "))]
    assert imports and not [l for l in imports if "tensorframes" in l or "perf" in l]
    for word in ("ragged_dot", "pallas", "lax.sort", "lax.scan", "cumsum"):
        assert word not in text.split('"""', 2)[2]


@pytest.mark.parametrize("seed", [0, 7, 2147483659])
def test_map_blocks_matches_the_reference(seed):
    """Float32 on both sides, the kernels interpreted: the four outputs
    part by the order of their float32 sums alone; the routing and the
    selection are the reference's own, exactly."""
    weights, params = _seeded(SMALL, seed)
    toks, frame = _frame(seed=seed % 1000)
    lp, load, choice, keys = _score(SMALL, frame, params)
    want_lp, want_load, want_choice, want_keys = (
        np.asarray(a) for a in ref.forward(SMALL, weights, toks, held=HELD))
    assert lp.dtype == np.float32 and load.dtype == np.int32 and keys.dtype == np.int16
    assert load.shape == (2, 4, 16) and choice.shape == (2, 4, 64, 4)
    assert keys.shape == (2, 2, 64, 16)
    np.testing.assert_allclose(lp, want_lp, atol=2e-5)
    np.testing.assert_array_equal(load, want_load)
    np.testing.assert_array_equal(np.sort(choice, -1), np.sort(want_choice, -1))
    np.testing.assert_array_equal(np.sort(keys, -1), np.sort(want_keys, -1))
    read = ref.compare((lp, load, choice, keys), (want_lp, want_load, want_choice, want_keys), 4)
    assert read["routing_swapped_share"] == read["index_swapped_share"] == 0
    assert (lp[:, -1] == 0).all() and (lp[:, :-1] < 0).all()


@pytest.mark.parametrize("seed", [1, 3])
def test_index_choice_is_the_references_own_top_k(seed):
    """Query t names min(t + 1, 16) distinct keys s <= t, -1 after: the
    reference's own top-16 of its index scores, exactly (both full layers)."""
    weights, params = _seeded(SMALL, seed)
    toks, frame = _frame(seed=seed)
    keys = _score(SMALL, frame, params)[3].astype(np.int64)
    own = np.asarray(ref.forward(SMALL, weights, toks, held=HELD)[3])
    t = np.arange(64)[:, None]
    assert ref._keys_valid(keys)
    assert (keys[..., np.arange(16)[None, :] > t] == -1).all()
    np.testing.assert_array_equal(keys[..., :1, :1], 0)  # position 0 keeps itself
    np.testing.assert_array_equal(np.sort(keys, -1), np.sort(own, -1))
    assert not np.array_equal(np.sort(keys[:, 0], -1), np.sort(keys[:, 1], -1))


def _top_k_oracle(scores, top):
    """The selection as the earlier program took it, kept here as the
    oracle: `lax.top_k` (a sort) over each query's scores, ties at the
    top-th kept from the lower key on, then s <= t."""
    seq = scores.shape[1]
    i32 = jnp.int32
    kept, keys = jax.lax.top_k(scores, top)
    kth = kept[..., -1:]
    tied = jnp.max(jnp.where(kept == kth, keys, i32(-1)), axis=-1, keepdims=True)
    s_at, at = jax.lax.iota(i32, seq), jax.lax.iota(i32, seq)[:, None]
    chosen = ((scores > kth) | ((scores == kth) & (s_at <= tied))) & (s_at <= at)
    keys = jnp.where(jax.lax.iota(i32, top)[None, :] <= at, keys, i32(-1))
    return np.asarray(chosen.astype(jnp.int8)), np.asarray(keys)


def _planted_scores(kind, seq, seed=0):
    """Index scores of two rows, -1e30 after each query, with ties planted."""
    rng = np.random.RandomState(seed)
    x = rng.randn(2, seq, seq).astype(np.float32)
    if kind in ("zeros", "signed_zeros"):  # every head's ReLU 0: exactly 0.0
        zero = rng.rand(*x.shape) < 0.7
        x[zero] = 0.0
        if kind == "signed_zeros":
            x[zero & (rng.rand(*x.shape) < 0.5)] = -0.0
    elif kind == "duplicates":  # a few values, so the k-th place straddles ties
        x = rng.randint(0, 4, size=x.shape).astype(np.float32)
    causal = np.arange(seq)[None, :] <= np.arange(seq)[:, None]
    return np.where(causal, x, np.float32(-1e30))


@pytest.mark.parametrize("kind,seq,topk,block", [
    ("zeros", 64, 16, 16),  # the first block wholly below k
    ("duplicates", 64, 16, 16),
    ("signed_zeros", 64, 16, 16),
    ("tail", 64, 24, 16),  # a block across k: queries t + 1 < k beside t + 1 > k
    ("duplicates", 60, 16, 16),  # seq not a multiple of the block
    ("zeros", 40, 64, 16),  # seq <= index_topk: k is the window
    ("duplicates", 200, 32, 64),  # lanes past the window
])
def test_the_selection_is_the_sorts_selection(monkeypatch, kind, seq, topk, block):
    """`_select` on planted index scores (the indexer kernel stood in for)
    against the sort it replaced: the same mask bit for bit, the same keys
    a query, ascending, -1 exactly past min(t + 1, k). -0.0 counts as +0.0:
    `lax.top_k` ranks -0.0 below +0.0, so on mixed-sign zeros at the k-th
    place the sort's own mask held more than k keys, not its key list (the
    indexer kernel's sums start at +0.0 and never give -0.0)."""
    planted = _planted_scores(kind, seq)
    pad = (-seq) % block
    padded = jnp.asarray(np.pad(planted, ((0, 0), (0, pad), (0, 0))))
    monkeypatch.setattr(lm, "INDEX_QUERIES", block)
    monkeypatch.setattr(lm, "index_scores", lambda q, k, w, start, **_: (
        jax.lax.dynamic_slice_in_dim(padded, start, block, axis=1)))
    cfg = lm.family_keys(dict(SMALL, index_topk=topk))
    _, params = _seeded(SMALL, 0)
    u = _layer_input(seq=seq)
    c_q = lm._latent_project(cfg, lm._at(params["mla"], 0), u)[0]
    chosen, keys = (np.asarray(a) for a in lm._select(
        cfg, lm._at(params["index"], 0), u, c_q, True))
    top = min(topk, seq)
    want, want_keys = _top_k_oracle(jnp.asarray(planted) + 0.0, top)  # -0.0 -> +0.0
    assert chosen.dtype == np.int8 and keys.shape == (2, seq, top)
    np.testing.assert_array_equal(chosen, want)
    np.testing.assert_array_equal(np.sort(keys, -1), np.sort(want_keys, -1))
    named = np.minimum(np.arange(seq) + 1, top)[:, None]
    assert ((keys >= 0) == (np.arange(top)[None, :] < named)).all()
    listed = np.where(keys >= 0, keys, seq + np.arange(top))
    assert (np.diff(listed, axis=-1) > 0).all()
    assert ref._keys_valid(keys)
    np.testing.assert_array_equal(chosen.sum(-1), np.broadcast_to(named[:, 0], (2, seq)))
    if kind == "signed_zeros":
        assert (_top_k_oracle(jnp.asarray(planted), top)[0].sum(-1) > named[:, 0]).any()


@pytest.mark.parametrize("topk,seq,want", [
    (None, 64, (2, 0)),  # the small preset's window
    (None, 40, (2, 0)),
    (64, 64, (0, 2)),  # k is the window: every block keeps every causal key
    ("cell", 16384, (28, 4)),  # hy4_score_16k: blocks 0 and 1 of 16 lie below 2,048
])
def test_the_blocks_each_selection_takes(topk, seq, want):
    cfg = (model_config(FILE, False)[0] if topk == "cell"
           else dict(SMALL, **({"index_topk": topk} if topk else {})))
    assert lm._index_blocks(lm.family_keys(cfg), seq) == want


def _layer_input(seed=4, seq=64):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(2, seq, 64), jnp.float32)


def test_a_shared_layer_takes_the_full_layers_keys_and_has_no_indexer():
    """A shared layer's output follows the selection the full layer before
    it made (other indexer weights there, another output here), and holds
    no indexer of its own: its stack has a row a full layer, and a shared
    layer given a NaN indexer still answers."""
    cfg = lm.family_keys(SMALL)
    _, params = _seeded(SMALL, 2)
    assert params["index"]["w_q"].shape[0] == 2  # full, full, shared, shared, shared
    u = _layer_input()
    p2 = lm._at(params["mla"], 2)
    c_q = lm._latent_project(cfg, lm._at(params["mla"], 1), u)[0]
    made = lm._select(cfg, lm._at(params["index"], 1), u, c_q, True)
    other = {**params["index"], "w_k": params["index"]["w_k"].at[1].multiply(-1.0)}
    made2 = lm._select(cfg, lm._at(other, 1), u, c_q, True)
    assert not np.array_equal(np.asarray(made[0]), np.asarray(made2[0]))
    y, handed = lm._sparse_attention_op(cfg, p2, params["index"], u, made, jnp.int32(-1), True)
    y2, _ = lm._sparse_attention_op(cfg, p2, params["index"], u, made2, jnp.int32(-1), True)
    assert np.abs(np.asarray(y) - np.asarray(y2)).max() > 1e-3
    np.testing.assert_array_equal(np.asarray(handed[1]), np.asarray(made[1]))
    poison = jax.tree_util.tree_map(lambda a: jnp.full_like(a, jnp.nan), params["index"])
    y3, _ = lm._sparse_attention_op(cfg, p2, poison, u, made, jnp.int32(-1), True)
    np.testing.assert_array_equal(np.asarray(y3), np.asarray(y))
    # and end to end: the first shared layer's keys are the second full layer's
    toks, frame = _frame(seed=2)
    sound = _score(SMALL, frame, params)
    changed = _score(SMALL, frame, {**params, "index": other})
    assert not np.array_equal(sound[3][:, 1], changed[3][:, 1])
    np.testing.assert_array_equal(sound[3][:, 0], changed[3][:, 0])


@pytest.mark.parametrize("seq", [64, 40])
def test_with_every_key_kept_the_sparse_layer_is_dense_attention_with_sink_and_gate(seq):
    """index_topk at least the window: every query keeps every key up to
    it, and the sublayer is a dense causal softmax with the sink in its
    denominator, gated, through W_o (numpy, float64)."""
    cfg = lm.family_keys(dict(SMALL, index_topk=64))
    _, params = _seeded(dict(SMALL, index_topk=64), 5)
    u = _layer_input(seed=6, seq=seq)
    p = lm._at(params["mla"], 0)
    nothing = (jnp.zeros((2, seq, seq), jnp.int8), jnp.full((2, seq, seq), -1, jnp.int32))
    got, (chosen, keys) = lm._sparse_attention_op(
        cfg, p, params["index"], u, nothing, jnp.int32(0), True)
    np.testing.assert_array_equal(np.asarray(chosen[0]), np.tril(np.ones((seq, seq))))
    _, q_n, q_r, kv, k_r = (np.asarray(a, np.float64) for a in lm._latent_project(cfg, p, u))
    z = (np.einsum("rhtd,rhsd->rhts", q_n, kv[..., :16])
         + np.einsum("rhtd,rsd->rhts", q_r, k_r[:, 0])) / np.sqrt(24)
    z = np.where(np.tril(np.ones((seq, seq), bool)), z, -np.inf)
    sink = np.asarray(p["sink"], np.float64)[None, :, None, None]
    top = np.maximum(z.max(-1, keepdims=True), sink)
    e = np.exp(z - top)
    o = np.einsum("rhts,rhsd->rhtd", e / (e.sum(-1, keepdims=True) + np.exp(sink - top)),
                  kv[..., 16:])
    o = np.swapaxes(o, 1, 2).reshape(2, seq, -1)
    gate = 1 / (1 + np.exp(-np.asarray(u, np.float64) @ np.asarray(p["w_g"], np.float64)))
    want = (o * gate) @ np.asarray(p["w_o"], np.float64)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_sinkhorn_mixing_is_doubly_stochastic(seed):
    cfg = lm.family_keys(SMALL)
    _, params = _seeded(SMALL, seed)
    X = jnp.asarray(np.random.RandomState(seed).randn(2, 16, 4, 64), jnp.float32)
    for j in range(2):
        links = jax.tree_util.tree_map(lambda a: a[seed % 5, j], params["hc"])
        pre, post, m = (np.asarray(a) for a in lm._hc_coefficients(cfg, links, X))
        np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-5)
        np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
        assert (m > 0).all() and (0 < pre).all() and (pre < 1).all()
        assert (0 < post).all() and (post < 2).all()
        assert m.std(axis=(0, 1)).max() > 1e-3  # it moves with the token


def test_two_shares_of_eight_experts_and_the_shared_expert_once_add_up():
    """The guide's share test: each share routes over all 16 experts and
    computes its own 8 (clamped SwiGLU); with what every chip computes
    alike, the shared expert, counted once, the two parts add up to the
    uncut reference's expert layer."""
    full = dict(SMALL, n_routed_experts=16)
    weights, _ = _seeded(full, 3, (0, 16))
    f = weights["layers"][2]["ffn"]
    spec = ref.spec_of(full)
    u = _layer_input(seed=8)
    whole, load, _ = ref.experts(u, f, None, spec=spec, held=(0, 16), operands="float32",
                                 sum_chunk=0)
    x = u.reshape(-1, 64)
    idx, w = moe.route(x, f["router"], None, top_k=4, scale=SMALL["routed_scaling_factor"])
    up = jnp.concatenate([f["w1"], f["w3"]], axis=-1)
    parts = [moe.held_experts(x, idx, w, up[a:a + 8], f["w2"][a:a + 8], (a, 8),
                              experts=16, limit=SMALL["swiglu_limit"]) for a in (0, 8)]
    shared = lm._dense_ffn({"w_up": jnp.concatenate([f["shared_w1"], f["shared_w3"]], -1),
                            "w_down": f["shared_w2"]}, x, "swiglu", SMALL["swiglu_limit"])
    np.testing.assert_allclose(sum(parts) + shared, np.asarray(whole).reshape(-1, 64),
                               atol=5e-5)
    assert int(np.sum(load)) == 4 * 2 * 64 and not np.allclose(parts[0], parts[1])
    for a, part in zip((0, 8), parts):  # a share alone is the reference given that share
        share = {**f, **{n: f[n][a:a + 8] for n in ("w1", "w3", "w2")}}
        want, _, _ = ref.experts(u, share, None, spec=spec, held=(a, 8), operands="float32",
                                 sum_chunk=0)
        np.testing.assert_allclose(part + shared, np.asarray(want).reshape(-1, 64), atol=5e-5)


@pytest.mark.parametrize("limit", [None, 1.0])
def test_the_clamped_swiglu(limit):
    h = jnp.asarray([[-3.0, 0.5, 2.0, -4.0, 0.3, 5.0]])
    g, up = h[:, :3], h[:, 3:]
    if limit:
        g, up = jnp.minimum(g, limit), jnp.clip(up, -limit, limit)
    np.testing.assert_allclose(moe.activation("swiglu", h, limit), jax.nn.silu(g) * up)


def _scopes(cfg, held=None):
    params = jax.eval_shape(lambda: lm.init_params(cfg, 0, held))
    fn = lm.scoring_fn(cfg, held=held, interpret=True)
    text = jax.jit(fn).lower(jnp.zeros((1, 32), jnp.int32), params).as_text(debug_info=True)
    return set(re.findall(r"(lm\.(?:hc|dsa_index|dsa)|mla\.gate)\b", text))


def _joyai_small():
    with open(os.path.join(ROOT, "perf", "configs", "joyai-llm-flash.json")) as f:
        joyai = json.load(f)
    return {**{k: v for k, v in joyai.items() if k not in joyai["derived"]},
            **joyai["presets"]["small"]}


def test_configurations_without_these_keys_trace_none_of_it():
    assert _scopes(_joyai_small()) == set()
    lfm2 = dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, conv_L_cache=3, rope_theta=1e6, norm_eps=1e-5,
        vocab_size=256, num_dense_layers=1,
        layer_types=["conv", "full_attention", "conv", "conv", "conv"],
        use_expert_bias=True, dtype="float32",
    )
    assert _scopes(lfm2) == set()
    assert _scopes(SMALL, HELD) == {"lm.hc", "lm.dsa_index", "lm.dsa", "mla.gate"}


def test_the_other_families_keep_their_trees_and_the_bias():
    joyai = _joyai_small()
    tree = lm.init_params(joyai, 0)
    assert set(tree) == {"embed", "head", "final_norm", "op_norm", "ffn_norm",
                         "mla", "dense", "moe"}
    assert set(tree["mla"]) == {"w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb", "w_o"}
    assert "bias" in tree["moe"] and "hc" not in tree
    assert "bias" not in lm.init_params(SMALL, 0, HELD)["moe"]  # no topk_method here


def test_init_params_and_the_benchmarks_weights_have_one_layout():
    cfg = dict(SMALL, dtype="bfloat16")
    own = lm.init_params(cfg, 0, HELD)
    w = lm_weights_sparse.weights(cfg, 0, HELD)
    filled = lm_weights_sparse.program_params(cfg, w)
    shape = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), t)
    assert shape(own) == shape(filled)
    assert set(own) == {"embed", "head", "final_norm", "op_norm", "ffn_norm", "mla", "index",
                        "dense", "moe", "hc", "hc_head"}
    L3, L1 = w["layers"][3], w["layers"][1]
    np.testing.assert_array_equal(filled["hc"]["phi"][3, 1][:, 8:], L3["hc_ffn"]["phi_res"])
    np.testing.assert_array_equal(filled["hc"]["bias"][3, 0][4:8], L3["hc_op"]["b_post"])
    np.testing.assert_array_equal(filled["index"]["w_k"][1], L1["op"]["w_kI"])
    np.testing.assert_array_equal(filled["mla"]["sink"][3], L3["op"]["sink"])
    np.testing.assert_array_equal(filled["moe"]["w_up"][2][:, :, 32:], L3["ffn"]["w3"])
    assert "w_qI" not in L3["op"] and "w_qI" in L1["op"]
    b_res = np.asarray(L3["hc_op"]["b_res"], np.float32).reshape(4, 4)
    assert np.diag(b_res).mean() > b_res[~np.eye(4, dtype=bool)].mean() + 1.0


@pytest.mark.parametrize("key,value", [
    ("gating_type", "headwise"), ("use_dsa", False), ("use_mla", False),
    ("indexer_types", ["shared", "full", "shared", "shared", "shared"]),
    ("indexer_types", ["full", "sparse", "shared", "shared", "shared"]),
    ("mlp_layer_types", ["sparse", "dense", "sparse", "sparse", "sparse"]),
    ("layer_types", ["deepseek_sparse_attention"] * 4 + ["full_attention"]),
    ("rope_parameters", {"rope_theta": 1e7, "rope_type": "yarn"}),
    ("num_hidden_layers", 6), ("n_group", 2),
])
def test_what_is_not_computed_raises_by_its_key(key, value):
    cfg = dict(SMALL, **{key: value})
    for call in (lambda: lm.scoring_fn(cfg), lambda: lm.init_params(cfg, 0)):
        with pytest.raises(ValueError, match=key):
            call()


def test_this_familys_names_give_its_plan():
    keys = lm.family_keys(SMALL)
    assert keys["layer_types"] == ["sparse_attention"] * 5
    assert (keys["num_dense_layers"], keys["num_experts"], keys["rope_theta"]) == (1, 16, 1e7)
    assert not keys.get("use_expert_bias") and keys["norm_eps"] == 1e-5
    np.testing.assert_array_equal(lm._full_index(keys), [0, 1, -1, -1, -1])
    np.testing.assert_array_equal(lm.layer_plan(SMALL), [
        [lm.SPARSE, 0, 0, 0], [lm.SPARSE, 1, 1, 0], [lm.SPARSE, 2, 1, 1],
        [lm.SPARSE, 3, 1, 2], [lm.SPARSE, 4, 1, 3]])


def test_the_scoring_program_holds_no_64_bit_array():
    cfg = dict(SMALL, dtype="bfloat16")
    params = lm.init_params(cfg, 0, HELD)
    text = str(jax.make_jaxpr(lm.scoring_fn(cfg, held=HELD, interpret=True))(
        jnp.zeros((2, 32), jnp.int32), params))
    assert not re.findall(r":[a-z]+64\[\d[^\n]*", text)


def test_bfloat16_weights_stay_near_the_reference():
    cfg = dict(SMALL, dtype="bfloat16", initializer_range=0.05, query_out_range=0.1)
    weights, params = _seeded(cfg, 3)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree_util.tree_leaves(params))
    toks, frame = _frame()
    got = _score(cfg, frame, params)
    same = ref.compare(got, ref.forward(cfg, weights, toks, held=HELD, routing=got[2],
                                        selection=got[3], operands="bfloat16"), 4)
    f32 = ref.compare(got, ref.forward(cfg, weights, toks, held=HELD, routing=got[2],
                                       selection=got[3]), 4)
    # two computations at one precision share little of five layers' rounding
    # noise: the stated precision reads no nearer than exact float32 here
    assert max(same["logprob_p99_abs_err"], f32["logprob_p99_abs_err"]) < 0.1
    assert same["expert_load_l1_share"] == f32["expert_load_l1_share"] == 0
    assert same["routing_swapped_share"] < 0.1 and same["index_swapped_share"] < 0.1


def test_the_counters_of_a_call_and_diagnostics():
    params = lm.init_params(SMALL, 0, HELD)
    _, frame = _frame(rows=2, seq=40, blocks=1)
    before = dict(tele.flat_counters())
    lm.score(lm.scoring_fn(SMALL, held=HELD, interpret=True), frame, params, SMALL)
    counters = tele.flat_counters()
    got = lambda k: counters.get(k, 0) - before.get(k, 0)
    assert got("lm.tokens") == 2 * 40
    kept = 16 * 17 // 2 + (40 - 16) * 16  # Σ_t min(t + 1, 16)
    assert got("lm.dsa_selected_pairs") == 2 * kept * 4 * 5
    assert got("lm.dsa_index_pairs") == 2 * (40 * 41 // 2) * 2 * 2
    assert got("lm.index_reuses") == 2 * 3
    # one block of 40 queries a full layer, above the top-16: by threshold
    assert got("lm.index_threshold_blocks") == 2 * 2 and got("lm.index_prefix_blocks") == 0
    assert got("lm.hc_stream_bytes") == 4 * 64 * 4 * 80 * 2 * 5
    assert got("moe.held_rows_expected") == got("moe.routed_rows") / 2
    assert got("lm.attention_pairs") == 0
    data = tfs.diagnostics(format="json")
    for name in ("lm.dsa_selected_pairs", "lm.dsa_index_pairs", "lm.index_reuses",
                 "lm.index_threshold_blocks", "lm.index_prefix_blocks", "lm.hc_stream_bytes"):
        assert data["model"][name] == counters[name]
    assert "lm.dsa_selected_pairs" in tfs.diagnostics()


def _index_inputs(seed, rows=2, seq=64, heads=2, width=16):
    rng = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    return f(rows, seq, heads, width), f(rows, seq, width), f(rows, seq, heads)


@pytest.mark.parametrize("start,block_k", [(0, 16), (16, 32), (48, 16), (32, 64)])
def test_the_index_kernel_is_the_causal_relu_score(start, block_k):
    q, k, w = _index_inputs(start)
    got = np.asarray(index_scores(q[:, start:start + 16], k, w[:, start:start + 16],
                                  jnp.int32(start), scale=0.25, block_k=block_k,
                                  interpret=True))
    s = np.einsum("rthd,rsd->rhts", q[:, start:start + 16], k)
    want = 0.25 * np.einsum("rth,rhts->rts", w[:, start:start + 16], np.maximum(s, 0))
    causal = np.arange(64)[None, :] <= start + np.arange(16)[:, None]
    assert got.shape == (2, 16, 64)
    np.testing.assert_allclose(got[:, causal], want[:, causal], rtol=1e-5, atol=1e-5)
    assert (got[:, ~causal] == -1e30).all()


@pytest.mark.parametrize("with_sink", [True, False])
@pytest.mark.parametrize("block", [16, 64])
def test_the_sparse_kernel_is_a_selected_softmax(with_sink, block):
    rng = np.random.RandomState(block)
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q, k, v, q2, k2 = f(2, 4, 64, 16), f(2, 4, 64, 16), f(2, 4, 64, 8), f(2, 4, 64, 8), f(2, 1, 64, 8)
    causal = np.tril(np.ones((64, 64), bool))
    chosen = ((rng.rand(2, 64, 64) < 0.3) | np.eye(64, dtype=bool)) & causal
    sink = f(4) if with_sink else None
    got = sparse_attention(q, k, v, jnp.asarray(chosen, jnp.int8), sink, q2=q2, k2=k2,
                           scale=0.2, block=block, interpret=True)
    z = 0.2 * (np.einsum("rhtd,rhsd->rhts", q, k) + np.einsum("rhtd,rsd->rhts", q2, k2[:, 0]))
    z = np.where(chosen[:, None], z, -np.inf)
    top = z.max(-1, keepdims=True)
    den_sink = 0.0
    if with_sink:
        s_ = np.asarray(sink)[None, :, None, None]
        top = np.maximum(top, s_)
        den_sink = np.exp(s_ - top)
    e = np.exp(z - top)
    want = np.einsum("rhts,rhsd->rhtd", e / (e.sum(-1, keepdims=True) + den_sink), v)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
