"""`models.lm` with layers of one mixer (a Mamba-2 mixer on the chunked
state-space scan kernel, relu² experts in a latent beside a shared expert,
attention without rotation), through the verb path at the small preset of
the benchmark's `nemotron-3-super-120b-a12b` (d = 64, pattern MEM*E, 16
Mamba heads of 8, 2 groups, state 16, chunk 16, 4 query / 2 key heads of
16, 16 experts top-4 in a latent of 32 with a quarter of them held, shared
96, vocabulary 512, float32), against the plain reference
`tests/references/nemotron_h.py`; the scan kernel against the recurrence
one position at a time; the expert layer at a held share against the path
it had; and what the two families that were there still are.
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
from perf.lib import lm_weights, lm_weights_hybrid, lm_weights_latent
from perf.runners.map_blocks_lm_hybrid import model_config
from tensorframes_tpu.models import lm, moe
from tensorframes_tpu.ops.pallas_kernels import ssd_scan
from tensorframes_tpu.utils import telemetry as tele

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(HERE, "references", "nemotron_h.py"), "nemotron_h_reference")

with open(os.path.join(ROOT, "perf", "configs", "nemotron-3-super-120b-a12b.json")) as f:
    FILE = json.load(f)
# the file at its small preset as the benchmark's runner hands it over: the
# router's width under the published key, and the share held here
SMALL, HELD = model_config(FILE, True)
OUTPUTS = ("token_logprob", "expert_load", "expert_choice")


def _frame(rows=2, seq=64, blocks=2, seed=0):
    toks = np.random.RandomState(seed).randint(0, 512, size=(rows, seq))
    offsets = [int(v) for v in np.linspace(0, rows, blocks + 1)]
    return toks, tfs.TensorFrame(
        [tfs.Column("tokens", jnp.asarray(toks, jnp.int32))], offsets
    )


def _seeded(cfg, seed, held):
    w = lm_weights_hybrid.weights(cfg, seed, held)
    return w, lm_weights_hybrid.program_params(cfg, w)


def _score(cfg, frame, params, held):
    return lm.score(lm.scoring_fn(cfg, held=held, interpret=True), frame, params, cfg)


def test_the_two_copies_of_the_reference_are_one_file():
    assert filecmp.cmp(
        os.path.join(ROOT, "perf", "configs", "nemotron-3-super-120b-a12b.reference.py"),
        os.path.join(HERE, "references", "nemotron_h.py"), shallow=False,
    )


def test_the_reference_imports_nothing_of_the_package():
    with open(os.path.join(HERE, "references", "nemotron_h.py")) as f:
        text = f.read()
    imports = [l for l in text.splitlines() if l.startswith(("import ", "from "))]
    assert imports and not [l for l in imports if "tensorframes" in l or "perf" in l]
    for word in ("ragged_dot", "pallas", "lax.sort", "argsort", "cumsum"):
        assert word not in text.split('"""', 2)[2]


@pytest.mark.parametrize("held", [HELD, (0, 16)], ids=["a_quarter", "all"])
@pytest.mark.parametrize("seed", [0, 7])
def test_map_blocks_matches_the_reference(seed, held):
    """Float32 on both sides, the kernels interpreted: they part by the
    order of their float32 sums alone (a chunked scan against a recurrence,
    a grouped matmul against a masked dense one), 1e-6 a layer; 2e-5 leaves
    room for five layers and the log-sum-exp."""
    weights, params = _seeded(SMALL, seed, held)
    toks, frame = _frame(seed=seed)
    out = _score(SMALL, frame, params, held)
    want_lp, want_load, want_choice = ref.forward(SMALL, weights, toks, held=held)
    got_lp, got_load, got_choice = (np.asarray(out[n].values) for n in OUTPUTS)
    assert got_lp.dtype == np.float32 and got_load.dtype == np.int32
    assert got_load.shape == (2, 2, 16) and got_choice.shape == (2, 2, 64, 4)
    np.testing.assert_allclose(got_lp, np.asarray(want_lp), atol=2e-5)
    np.testing.assert_array_equal(got_load, np.asarray(want_load))
    np.testing.assert_array_equal(
        np.sort(got_choice, -1), np.sort(np.asarray(want_choice), -1))
    # every token exactly 4 distinct experts of all 16, none dropped
    assert (np.diff(np.sort(got_choice, -1), axis=-1) > 0).all()
    counts = (got_choice[..., None] == np.arange(16)).sum(axis=(2, 3))
    np.testing.assert_array_equal(counts, got_load)
    assert (got_load.sum(-1) == 64 * 4).all()
    assert (got_lp[:, -1] == 0).all() and (got_lp[:, :-1] < 0).all()


def test_a_window_that_is_no_multiple_of_the_chunk():
    weights, params = _seeded(SMALL, 3, HELD)
    toks, frame = _frame(rows=2, seq=50, blocks=1, seed=3)
    out = _score(SMALL, frame, params, HELD)
    want_lp, want_load, _ = ref.forward(SMALL, weights, toks, held=HELD)
    np.testing.assert_allclose(
        np.asarray(out["token_logprob"].values), np.asarray(want_lp), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out["expert_load"].values), want_load)


def test_every_token_gets_exactly_22_distinct_experts():
    cfg = dict(SMALL, n_routed_experts=64, num_experts_per_tok=22)
    held = (16, 16)
    weights, params = _seeded(cfg, 1, held)
    toks, frame = _frame(rows=2, seq=48, blocks=1)
    out = _score(cfg, frame, params, held)
    load = np.asarray(out["expert_load"].values)
    choice = np.sort(np.asarray(out["expert_choice"].values), -1)
    assert choice.shape == (2, 2, 48, 22) and (np.diff(choice, axis=-1) > 0).all()
    assert load.shape == (2, 2, 64) and (load.sum(-1) == 48 * 22).all()
    assert (load <= 48).all()  # an expert at most once a token
    want_lp, want_load, _ = ref.forward(cfg, weights, toks, held=held)
    np.testing.assert_array_equal(load, np.asarray(want_load))
    np.testing.assert_allclose(
        np.asarray(out["token_logprob"].values), np.asarray(want_lp), atol=2e-5)


def test_each_mixer_is_in_the_result():
    """Taking the scan's state, a held expert, the latent's way out or the
    shared expert out of the program's weights moves the result: the
    comparison above would see a fault in them."""
    weights, params = _seeded(SMALL, 3, HELD)
    toks, frame = _frame(seed=3)
    sound = np.asarray(_score(SMALL, frame, params, HELD)["token_logprob"].values)
    zero = lambda kind, name: {**params, kind: {
        **params[kind], name: jnp.zeros_like(params[kind][name])}}
    no_expert = {**params, "moe": {**params["moe"], "w_down":
                 params["moe"]["w_down"].at[:, 1].set(0.0)}}
    inner = 16 * 8
    no_b = {**params, "ssm": {**params["ssm"], "w_in":  # B = silu(conv_b): no input
            params["ssm"]["w_in"].at[:, :, 2 * inner:2 * inner + 32].set(0.0)}}
    for broken in (no_expert, no_b, zero("moe", "latent_out"),
                   zero("moe", "shared_down"), zero("attn", "w_o")):
        got = np.asarray(_score(SMALL, frame, broken, HELD)["token_logprob"].values)
        assert np.abs(got - sound)[:, :-1].max() > 1e-3


def _recurrence(x, dt, A, B, C, D):
    """The scan one position at a time (the reference's own mathematics)."""
    rows, seq, heads, width = x.shape
    per = heads // B.shape[2]

    def step(S, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = jnp.repeat(b_t, per, axis=1), jnp.repeat(c_t, per, axis=1)
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return S, jnp.einsum("rhwn,rhn->rhw", S, c_t) + D[:, None] * x_t

    S0 = jnp.zeros((rows, heads, width, B.shape[3]), jnp.float32)
    _, y = jax.lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def _scan_inputs(seq, seed=0, rows=2, heads=16, width=8, groups=2, state=16):
    rng = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    # slow heads (A near 0) among fast ones: their state outlives many chunks
    A = -jnp.exp(jnp.asarray(rng.uniform(-4, 1, heads), jnp.float32))
    return (f(rows, seq, heads, width), jax.nn.softplus(f(rows, seq, heads)), A,
            f(rows, seq, groups, state), f(rows, seq, groups, state), f(heads))


@pytest.mark.parametrize("seq", [64, 50, 16, 7])
@pytest.mark.parametrize("chunk", [16, 8])
def test_the_scan_kernel_is_the_recurrence(seq, chunk):
    """A window that is and ones that are not a multiple of the chunk, a
    state that crosses three chunks and more, two chunk sizes: float32, the
    kernel interpreted, so the two part by the order of their sums."""
    args = _scan_inputs(seq, seed=seq)
    want = _recurrence(*args)
    got = ssd_scan(*args, chunk=chunk, interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)


def test_the_scans_state_crosses_chunks():
    """What position 0 put into the state still shows three chunks on: with
    x zero but at position 0 the output past the first chunk is the carried
    state's part alone."""
    x, dt, A, B, C, D = _scan_inputs(64, seed=5)
    x = x.at[:, 1:].set(0.0)
    got = np.asarray(ssd_scan(x, dt, A, B, C, D, chunk=16, interpret=True))
    want = np.asarray(_recurrence(x, dt, A, B, C, D))
    assert np.abs(want[:, 48:]).max() > 1e-3  # it is there, three boundaries on
    np.testing.assert_allclose(got, want, atol=1e-5)
    cut = np.asarray(ssd_scan(x[:, 16:], dt[:, 16:], A, B[:, 16:], C[:, 16:], D,
                              chunk=16, interpret=True))
    assert np.abs(cut).max() == 0.0  # a scan that starts after it sees none of it


def test_the_scan_kernel_in_bfloat16_and_its_refusals():
    x, dt, A, B, C, D = _scan_inputs(64, seed=2)
    bf = jnp.bfloat16
    got = ssd_scan(x.astype(bf), dt, A, B.astype(bf), C.astype(bf), D, chunk=16,
                   interpret=True)
    assert got.dtype == bf
    want = np.asarray(_recurrence(x, dt, A, B, C, D))
    err = np.abs(np.asarray(got, np.float32) - want)
    assert np.percentile(err, 99) < 0.05 * np.abs(want).max()
    with pytest.raises(NotImplementedError, match="no backward pass"):
        jax.grad(lambda a: ssd_scan(a, dt, A, B, C, D, chunk=16, interpret=True).sum())(x)
    with pytest.raises(ValueError, match="groups"):
        ssd_scan(x[:, :, :15], dt[:, :, :15], A[:15], B, C, D[:15], interpret=True)


def _expert_layer(seed=0, rows=48, d=64, latent=32, f=48, fs=96, e=16):
    rng = np.random.RandomState(seed)
    g = lambda scale, *shape: jnp.asarray(scale * rng.randn(*shape), jnp.float32)
    p = {
        "router": g(1.0, d, e), "bias": g(0.1, e),
        "w_l1": g(0.2, d, latent), "w_l2": g(0.2, latent, d),
        "w1": g(0.3, e, latent, f), "w2": g(0.3, e, f, latent),
        "shared_w1": g(0.2, d, fs), "shared_w2": g(0.2, fs, d),
    }
    return p, g(1.0, 1, rows, d)


SPEC = ref.spec_of(SMALL)


def test_four_shares_of_four_experts_and_the_shared_expert_once_add_up():
    """The guide's share test: each share routes over all 16 experts,
    computes its own 4 in the latent and takes their sum through W_l2; what
    every chip computes alike, the shared expert, is counted once."""
    p, h = _expert_layer()
    gain = jnp.ones((64,), jnp.float32)
    whole, load, _ = ref.experts(h, gain, p, None, spec=SPEC, held=(0, 16),
                                 operands="float32", sum_chunk=0)
    x = ref.rms_norm(h, gain, 1e-5)[0]
    idx, w = moe.route(x, p["router"], p["bias"], top_k=4, scale=5.0)
    x_l = x @ p["w_l1"]
    parts = [
        moe.held_experts(x_l, idx, w, p["w1"][first:first + 4], p["w2"][first:first + 4],
                         (first, 4), act="relu2", experts=16) @ p["w_l2"]
        for first in range(0, 16, 4)
    ]
    shared = lm._dense_ffn({"w_up": p["shared_w1"], "w_down": p["shared_w2"]}, x, "relu2")
    np.testing.assert_allclose(
        h[0] + sum(parts) + shared, np.asarray(whole[0]), atol=5e-5)
    assert int(np.sum(load)) == 4 * h.shape[1]
    assert not np.allclose(parts[0], parts[1])
    # a share alone is what the reference gives for that share (its own
    # experts through W_l2 and, there, the shared expert)
    for first, part in zip(range(0, 16, 4), parts):
        share = {**p, **{n: p[n][first:first + 4] for n in ("w1", "w2")}}
        want, _, _ = ref.experts(h, gain, share, None, spec=SPEC, held=(first, 4),
                                 operands="float32", sum_chunk=0)
        np.testing.assert_allclose(h[0] + part + shared, np.asarray(want[0]), atol=5e-5)


@pytest.mark.parametrize("act,side", [("swiglu", 2), ("relu2", 1), ("gelu", 1)])
@pytest.mark.parametrize("step", [8192, 40])
def test_a_held_share_is_the_old_paths_part_and_all_held_is_unchanged(
        act, side, step, monkeypatch):
    """`held_experts` told the router's width multiplies the held experts'
    rows alone, `STEP_ROWS` of the sorted order at a time: at a held
    quarter what the path it had (every routed row's place) gives, the
    shares adding up to the layer; holding all, the old path itself."""
    monkeypatch.setattr(moe, "STEP_ROWS", step)
    rng = np.random.RandomState(3)
    d, f, e, k, rows = 16, 24, 16, 4, 300
    g = lambda *shape: jnp.asarray(0.3 * rng.randn(*shape), jnp.float32)
    x, router, w_up, w_down = g(rows, d), g(d, e), g(e, d, side * f), g(e, f, d)
    idx, w = moe.route(x, router, None, top_k=k)
    whole = moe.held_experts(x, idx, w, w_up, w_down, (0, e), act=act)
    np.testing.assert_array_equal(
        moe.held_experts(x, idx, w, w_up, w_down, (0, e), act=act, experts=e), whole)
    shares = []
    for first in range(0, e, 4):
        own = (w_up[first:first + 4], w_down[first:first + 4], (first, 4))
        old = moe.held_experts(x, idx, w, *own, act=act)
        new = jax.jit(lambda x, idx, w: moe.held_experts(
            x, idx, w, *own, act=act, experts=e))(x, idx, w)
        np.testing.assert_allclose(new, old, atol=1e-6)
        shares.append(new)
    np.testing.assert_allclose(sum(shares), whole, atol=1e-5)
    # the stacked weights of several layers, this layer's groups among them
    stack = lambda a: jnp.stack([a * 0 + 7.0, a, a * 0 - 1.0])
    np.testing.assert_allclose(
        moe.held_experts(x, idx, w, stack(w_up[4:8]), stack(w_down[4:8]), (4, 4),
                         act=act, experts=e, layer=jnp.int32(1)), shares[1], atol=1e-6)


def test_part_sizes_take_the_experts_input_width_and_a_share_needs_no_parts(monkeypatch):
    """32,768 tokens top-22 in a 1,024 latent: a place for every routed
    row would need 8 parts (by the latent's width, not the residual's 16);
    the held share's loop keeps no such place and takes the layer whole."""
    assert moe.parts_for(32768, 22, 1024, 2688, 2688, 2) == 8
    assert moe.parts_for(32768, 22, 4096, 2688, 2688, 2) == 16
    monkeypatch.setattr(moe, "STEP_ROWS", 32)  # 256 routed rows: steps of 32
    text = str(jax.make_jaxpr(lambda x, i, w, a, b: moe.held_experts(
        x, i, w, a, b, (0, 4), act="relu2", experts=16))(
        jnp.zeros((64, 8), jnp.float32), jnp.zeros((64, 4), jnp.int32),
        jnp.zeros((64, 4), jnp.float32), jnp.zeros((4, 8, 6), jnp.float32),
        jnp.zeros((4, 6, 8), jnp.float32)))
    assert "while" in text and "scatter-add" in text and "f32[256," not in text
    with pytest.raises(ValueError, match="activation"):
        moe.activation("tanh", jnp.zeros((2, 4)))


def test_relu2_and_swiglu_experts_by_key():
    keys = lm.family_keys(SMALL)
    assert keys["ffn_act"] == "relu2"
    assert lm.init_params(SMALL, 0, HELD)["moe"]["w_up"].shape == (2, 4, 32, 48)
    gated = dict(SMALL, mlp_hidden_act="silu")
    assert lm.family_keys(gated)["ffn_act"] == "swiglu"
    params = lm.init_params(gated, 0, HELD)
    assert params["moe"]["w_up"].shape == (2, 4, 32, 96)  # gate | up side by side
    assert params["moe"]["shared_up"].shape == (2, 64, 192)
    _, frame = _frame(rows=1, seq=16, blocks=1)
    a = np.asarray(_score(gated, frame, params, HELD)["token_logprob"].values)
    assert np.isfinite(a).all() and (a[:, :-1] < 0).all()
    h = jnp.asarray([[-1.0, 2.0, 3.0, -4.0]])
    np.testing.assert_allclose(moe.activation("relu2", h), [[0.0, 4.0, 9.0, 0.0]])
    np.testing.assert_allclose(
        moe.activation("swiglu", h), jax.nn.silu(h[:, :2]) * h[:, 2:])


@pytest.mark.parametrize("key,value", [
    ("hybrid_override_pattern", "ME-*E"), ("n_group", 2), ("topk_group", 2),
    ("use_bias", True), ("mlp_bias", True), ("attention_bias", True),
    ("mamba_proj_bias", True), ("mlp_hidden_act", "gelu"), ("mamba_hidden_act", "relu"),
    ("use_conv_bias", False), ("sliding_window", 4096), ("norm_eps", 1e-6),
    ("num_hidden_layers", 6), ("expand", 3),
])
def test_what_is_not_computed_raises_by_its_key(key, value):
    cfg = dict(SMALL, **{key: value})
    for call in (lambda: lm.scoring_fn(cfg), lambda: lm.init_params(cfg, 0)):
        with pytest.raises(ValueError, match=key):
            call()


def test_this_familys_names_give_its_plan():
    keys = lm.family_keys(SMALL)
    assert keys["layer_types"] == ["ssm", "experts", "ssm", "full_attention", "experts"]
    assert (keys["num_dense_layers"], keys["num_experts"]) == (0, 16)
    assert keys["norm_eps"] == 1e-5 and keys["use_expert_bias"] is True
    assert keys["one_mixer"] and not keys["qk_norm"] and not keys["rope"]
    np.testing.assert_array_equal(lm.layer_plan(SMALL), [
        [lm.SSM, 0, lm.NO_FFN, 0], [lm.EXPERTS, 0, lm.NO_FFN, 0], [lm.SSM, 1, lm.NO_FFN, 0],
        [1, 0, lm.NO_FFN, 0], [lm.EXPERTS, 1, lm.NO_FFN, 0]])
    assert lm.held_all(SMALL) == (0, 16)


def _tree(t):
    return jax.tree_util.tree_map(lambda a: (tuple(a.shape), str(a.dtype)), t)


def test_the_two_families_that_were_there_are_what_they_were():
    """Their plans, their parameter trees and their keys, pinned: a layer
    that may be one part changed none of them."""
    lfm2 = dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, conv_L_cache=3, rope_theta=1e6, norm_eps=1e-5,
        vocab_size=256, num_dense_layers=1,
        layer_types=["conv", "full_attention", "conv", "conv", "conv"],
        use_expert_bias=True, dtype="float32",
    )
    assert lm.family_keys(lfm2) == lfm2  # nothing added, nothing renamed
    np.testing.assert_array_equal(lm.layer_plan(lfm2), [
        [0, 0, 0, 0], [1, 0, 1, 0], [0, 1, 1, 1], [0, 2, 1, 2], [0, 3, 1, 3]])
    f32 = "float32"
    assert _tree(lm.init_params(lfm2, 0)) == {
        "embed": ((256, 64), f32), "head": ((64, 256), f32), "final_norm": ((64,), f32),
        "op_norm": ((5, 64), f32), "ffn_norm": ((5, 64), f32),
        "conv": {"w_in": ((4, 64, 192), f32), "taps": ((4, 3, 64), f32),
                 "w_out": ((4, 64, 64), f32)},
        "attn": {"w_qkv": ((1, 64, 128), f32), "q_norm": ((1, 16), f32),
                 "k_norm": ((1, 16), f32), "w_o": ((1, 64, 64), f32)},
        "dense": {"w_up": ((1, 64, 256), f32), "w_down": ((1, 128, 64), f32)},
        "moe": {"router": ((4, 64, 8), f32), "bias": ((4, 8), f32),
                "w_up": ((4, 8, 64, 64), f32), "w_down": ((4, 8, 32, 64), f32)},
    }
    with open(os.path.join(ROOT, "perf", "configs", "joyai-llm-flash.json")) as f:
        joyai = json.load(f)
    joyai = {**{k: v for k, v in joyai.items() if k not in joyai["derived"]},
             **joyai["presets"]["small"]}
    np.testing.assert_array_equal(
        lm.layer_plan(joyai), [[2, 0, 0, 0], [2, 1, 1, 0], [2, 2, 1, 1]])
    tree = _tree(lm.init_params(joyai, 0))
    assert set(tree) == {"embed", "head", "final_norm", "op_norm", "ffn_norm",
                         "mla", "dense", "moe"}
    assert tree["moe"] == {
        "router": ((2, 64, 16), f32), "bias": ((2, 16), f32),
        "w_up": ((2, 16, 64, 64), f32), "w_down": ((2, 16, 32, 64), f32),
        "shared_up": ((2, 64, 64), f32), "shared_down": ((2, 32, 64), f32)}
    assert tree["dense"] == {"w_up": ((1, 64, 256), f32), "w_down": ((1, 128, 64), f32)}
    assert set(tree["mla"]) == {"w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb", "w_o"}
    # and the same seed still draws the same numbers for them
    for cfg, maker in ((lfm2, lm_weights), (joyai, lm_weights_latent)):
        a, b = lm.init_params(cfg, 5), lm.init_params(cfg, 5)
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda x, y: bool((x == y).all()), a, b))
        assert _tree(maker.program_params(cfg, maker.weights(cfg, 5))) == _tree(a)


def test_init_params_and_the_benchmarks_weights_have_one_layout():
    cfg = dict(SMALL, dtype="bfloat16")
    own = lm.init_params(cfg, 0, HELD)
    w = lm_weights_hybrid.weights(cfg, 0, HELD)
    filled = lm_weights_hybrid.program_params(cfg, w)
    assert _tree(own) == _tree(filled)
    assert set(own) == {"embed", "head", "final_norm", "op_norm", "ssm", "attn", "moe"}
    assert set(own["attn"]) == {"w_qkv", "w_o"}  # no q/k norm in this family
    assert set(own["ssm"]) == {"w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                               "norm", "w_out"}
    assert own["moe"]["w_up"].shape == (2, 4, 32, 48) and own["moe"]["router"].shape == (2, 64, 16)
    m, e = w["layers"][2]["mixer"], w["layers"][4]["mixer"]
    inner, gn = 128, 32
    np.testing.assert_array_equal(filled["ssm"]["w_in"][1][:, :inner], m["w_z"])
    np.testing.assert_array_equal(
        filled["ssm"]["w_in"][1][:, 2 * inner + gn:2 * inner + 2 * gn], m["w_C"])
    np.testing.assert_array_equal(filled["ssm"]["w_in"][1][:, -16:], m["w_dt"])
    np.testing.assert_array_equal(filled["ssm"]["conv_w"][1][:, inner:inner + gn], m["conv_B"])
    np.testing.assert_array_equal(filled["ssm"]["conv_b"][1][-gn:], m["conv_bC"])
    np.testing.assert_array_equal(filled["ssm"]["norm"][1], m["gate_norm"])
    np.testing.assert_array_equal(filled["moe"]["latent_out"][1], e["w_l2"])
    np.testing.assert_array_equal(filled["moe"]["w_down"][1], e["w2"])
    np.testing.assert_array_equal(filled["op_norm"][4], w["layers"][4]["norm"])
    att = w["layers"][3]["mixer"]
    np.testing.assert_array_equal(filled["attn"]["w_qkv"][0][:, 64:96], att["wk"])
    # the initialiser's own rules
    a = np.asarray(own["ssm"]["A_log"], np.float32)
    assert (a >= 0).all() and (a <= np.log(16.0) + 0.02).all()
    dt = np.log1p(np.exp(np.asarray(own["ssm"]["dt_bias"], np.float32)))
    assert (dt > 5e-4).all() and (dt < 0.11).all()
    assert (np.asarray(own["ssm"]["D"], np.float32) == 1).all()


def test_the_scoring_program_holds_no_64_bit_array():
    import re

    cfg = dict(SMALL, dtype="bfloat16")
    params = lm.init_params(cfg, 0, HELD)
    text = str(jax.make_jaxpr(lm.scoring_fn(cfg, held=HELD, interpret=True))(
        jnp.zeros((2, 32), jnp.int32), params))
    assert not re.findall(r":[a-z]+64\[\d[^\n]*", text)


def test_bfloat16_weights_stay_near_the_reference():
    cfg = dict(SMALL, dtype="bfloat16", initializer_range=0.05,
               expert_out_range=0.2, query_out_range=0.1)
    weights, params = _seeded(cfg, 3, HELD)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree_util.tree_leaves(params))
    toks, frame = _frame()
    out = _score(cfg, frame, params, HELD)
    got = [out[n].values for n in OUTPUTS]
    same = ref.compare(got, ref.forward(
        cfg, weights, toks, held=HELD, routing=got[2], operands="bfloat16"), 4)
    f32 = ref.compare(got, ref.forward(cfg, weights, toks, held=HELD, routing=got[2]), 4)
    assert same["logprob_p99_abs_err"] <= f32["logprob_p99_abs_err"] < 0.05
    assert same["expert_load_l1_share"] == f32["expert_load_l1_share"] == 0
    assert same["routing_swapped_share"] <= f32["routing_swapped_share"] < 0.1
    low = ref.forward(cfg, weights, toks, held=HELD, operands="bfloat16", sum_chunk=8)
    assert np.abs(np.asarray(low[0]) - np.asarray(got[0])).max() > 0


def test_the_counters_of_a_call():
    params = lm.init_params(SMALL, 0, HELD)
    _, frame = _frame(rows=2, seq=16, blocks=1)
    _score(SMALL, frame, params, HELD)
    counters = tele.flat_counters()
    assert counters["bindings.bytes_placed"] == 0
    assert counters["lm.tokens"] == 2 * 16
    assert counters["lm.ssm_steps"] == 2 * 16 * 2  # two Mamba-2 layers
    assert counters["moe.routed_rows"] == 2 * 16 * 4 * 2
    assert counters["moe.held_rows_expected"] == 2 * 16 * 4 * 2 / 4  # a quarter held
    assert counters["lm.attention_pairs"] == 2 * (16 * 17 // 2) * 4 * 1
