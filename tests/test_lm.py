"""`models.lm` through the verb path, at a small preset of the same
configuration code the benchmark's `lfm2-8b-a1b` runs at its published
widths (d = 64, 4 heads over 2 key/value heads, 8 experts top-2, layers
dense-conv, attention, conv, conv, conv, vocabulary 256, float32), against
the plain reference `tests/references/lfm2.py`; and what the function
front end promises of a bound pytree: one trace, one placement, the graph
front end's block loop and spans.
"""

import filecmp
import functools
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tensorframes_tpu as tfs
from perf.lib import lm_weights
from tensorframes_tpu.models import MoEFFN, lm, moe
from tensorframes_tpu.runtime import bindings as rb
from tensorframes_tpu.runtime.executor import Executor, FnProgram
from tensorframes_tpu.utils import telemetry as tele

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(HERE, "references", "lfm2.py"), "lfm2_reference")

SMALL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, conv_L_cache=3, rope_theta=1e6, norm_eps=1e-5,
    vocab_size=256, num_dense_layers=1,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    use_expert_bias=True, norm_topk_prob=True, routed_scaling_factor=1,
    dtype="float32", initializer_range=0.11,
)


def _frame(rows=4, seq=32, blocks=2, seed=0):
    toks = np.random.RandomState(seed).randint(0, 256, size=(rows, seq))
    offsets = [int(v) for v in np.linspace(0, rows, blocks + 1)]
    return toks, tfs.TensorFrame(
        [tfs.Column("tokens", jnp.asarray(toks, jnp.int32))], offsets
    )


def _seeded(cfg, seed):
    """(the reference's weights, the same numbers as the program's bound
    pytree): the benchmark's own generator, `perf/lib/lm_weights.py`."""
    w = lm_weights.weights(cfg, seed)
    return w, lm_weights.program_params(cfg, w)


def _score(cfg, frame, params):
    return lm.score(lm.scoring_fn(cfg, interpret=True), frame, params, cfg)


def test_the_two_copies_of_the_reference_are_one_file():
    assert filecmp.cmp(
        os.path.join(ROOT, "perf", "configs", "lfm2-8b-a1b.reference.py"),
        os.path.join(HERE, "references", "lfm2.py"), shallow=False,
    )


@pytest.mark.parametrize("seed", [0, 7])
@pytest.mark.parametrize("layers", [
    SMALL["layer_types"], ["conv", "conv"], ["full_attention", "conv"],
], ids=["period", "conv-only", "attention-first"])
def test_map_blocks_matches_the_reference(seed, layers):
    cfg = dict(SMALL, layer_types=layers)
    weights, params = _seeded(cfg, seed)
    toks, frame = _frame(seed=seed)
    out = _score(cfg, frame, params)
    want_lp, want_load, want_choice = ref.forward(cfg, weights, toks)
    got_lp = np.asarray(out["token_logprob"].values)
    got_load = np.asarray(out["expert_load"].values)
    got_choice = np.asarray(out["expert_choice"].values)
    assert got_lp.dtype == np.float32 and got_load.dtype == np.int32
    assert got_load.shape == (4, len(layers) - 1, 8)
    assert got_choice.shape == (4, len(layers) - 1, 32, 2)
    np.testing.assert_allclose(got_lp, np.asarray(want_lp), atol=2e-5)
    np.testing.assert_array_equal(got_load, np.asarray(want_load))
    np.testing.assert_array_equal(
        np.sort(got_choice, -1), np.sort(np.asarray(want_choice), -1))
    # the loads are the counts of the choices
    counts = (got_choice[..., None] == np.arange(8)).sum(axis=(2, 3))
    np.testing.assert_array_equal(counts, got_load)
    assert (got_lp[:, -1] == 0).all() and (got_lp[:, :-1] < 0).all()
    np.testing.assert_array_equal(np.asarray(out["tokens"].values), toks)


@pytest.mark.parametrize("held", [None, (2, 4)])
def test_init_params_and_the_benchmarks_weights_have_one_layout(held):
    """`lm.init_params` (a user's way to a model) and the pytree the
    benchmark fills from the reference's layout: same tree, shapes, dtypes."""
    cfg = dict(SMALL, dtype="bfloat16")
    own = lm.init_params(cfg, 0, held)
    filled = lm_weights.program_params(cfg, lm_weights.weights(cfg, 0, held))
    shape = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    assert shape(own) == shape(filled)
    w = lm_weights.weights(cfg, 0, held)["layers"][1]  # attention, experts
    hd = 64 // 4
    np.testing.assert_array_equal(
        filled["attn"]["w_qkv"][0][:, 4 * hd:6 * hd], w["op"]["wk"])
    np.testing.assert_array_equal(
        filled["moe"]["w_up"][0][:, :, 32:], w["ffn"]["w3"])
    np.testing.assert_array_equal(filled["op_norm"][1], w["op_norm"])


def test_the_scoring_program_holds_no_64_bit_array():
    """The package runs under x64, where a sum of int32 or a
    `take_along_axis` index is int64; the chip's grouped matmul compiles in
    no module that holds a 64-bit array (XLA's x64 rewriter has no rule for
    it: my chip run, PR 28)."""
    import re

    cfg = dict(SMALL, dtype="bfloat16")
    params = lm.init_params(cfg, 0)
    text = str(jax.make_jaxpr(lm.scoring_fn(cfg, interpret=True))(
        jnp.zeros((2, 32), jnp.int32), params))
    assert not re.findall(r":[a-z]+64\[\d[^\n]*", text)


def test_bfloat16_weights_stay_near_the_reference():
    cfg = dict(SMALL, dtype="bfloat16", initializer_range=0.02)
    weights, params = _seeded(cfg, 3)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree_util.tree_leaves(params))
    toks, frame = _frame()
    out = _score(cfg, frame, params)
    got = [out[n].values for n in ("token_logprob", "expert_load", "expert_choice")]
    # along the program's own routing, at the program's precision and
    # against plain float32: rounding alone, and no load is off
    same = ref.compare(got, ref.forward(
        cfg, weights, toks, routing=got[2], operands="bfloat16"), 2)
    f32 = ref.compare(got, ref.forward(cfg, weights, toks, routing=got[2]), 2)
    assert same["logprob_p99_abs_err"] < 2e-4 < f32["logprob_p99_abs_err"] < 0.01
    assert same["expert_load_l1_share"] == f32["expert_load_l1_share"] == 0
    assert same["routing_swapped_share"] <= f32["routing_swapped_share"] < 0.05


def test_compare_follows_a_routing_and_refuses_a_broken_one():
    cfg = dict(SMALL)
    weights, _ = _seeded(cfg, 2)
    toks, _ = _frame(rows=2, seq=24, blocks=1)
    lp, load, own = (np.asarray(a) for a in ref.forward(cfg, weights, toks))
    assert ref.compare((lp, load, own), (lp, load, own), 2) == dict.fromkeys(
        ref.NUMBERS, 0.0)
    # another routing for one token of the first expert layer: followed by
    # the reference it is no error, but it is not the reference's own
    other = own.copy()
    other[0, 0, 5] = [e for e in range(8) if e not in own[0, 0, 5]][:2]
    lp2, load2, own2 = (np.asarray(a) for a in ref.forward(
        cfg, weights, toks, routing=other))
    assert np.abs(lp2 - lp)[0, 5:].max() > 1e-4 and (lp2[1] == lp[1]).all()
    assert (lp2[0, :5] == lp[0, :5]).all()  # causal: nothing before moves
    np.testing.assert_array_equal(own2[0, 0], own[0, 0])
    read = ref.compare((lp2, load2, other), (lp2, load2, own2), 2)
    assert read["logprob_p99_abs_err"] == 0 and read["expert_load_l1_share"] == 0
    assert read["routing_swapped_share"] >= 1 / (2 * 4 * 24)
    # not top_k distinct experts of the model, a NaN, a wrong shape
    twice = own.copy(); twice[1, 2, 3] = twice[1, 2, 3, 0]
    beyond = own.copy(); beyond[0, 0, 0, 0] = 8
    nan = lp.copy(); nan[0, 3] = np.nan
    for got in ((lp, load, twice), (lp, load, beyond), (nan, load, own),
                (lp[:, :-1], load, own), (lp, load, own[..., :1])):
        assert ref.compare(got, (lp, load, own), 2) == dict.fromkeys(
            ref.NUMBERS, float("inf"))


def _moe_layer(seed=0, rows=40):
    rng = np.random.RandomState(seed)
    d, f, e = 16, 24, 8
    p = {
        "router": jnp.asarray(rng.randn(d, e), jnp.float32),
        "bias": jnp.asarray(0.1 * rng.randn(e), jnp.float32),
        "w1": jnp.asarray(0.3 * rng.randn(e, d, f), jnp.float32),
        "w3": jnp.asarray(0.3 * rng.randn(e, d, f), jnp.float32),
        "w2": jnp.asarray(0.3 * rng.randn(e, f, d), jnp.float32),
    }
    return p, jnp.asarray(rng.randn(1, rows, d), jnp.float32)


def _part(p, u, held):
    first, count = held
    x = u[0]
    idx, w = moe.route(x, p["router"], p["bias"], top_k=2)
    w_up = jnp.concatenate([p["w1"], p["w3"]], axis=-1)  # the program's storage
    return moe.held_experts(
        x, idx, w, w_up[first:first + count], p["w2"][first:first + count], held,
    )


def test_four_shares_of_two_experts_add_up_to_the_whole_layer():
    p, u = _moe_layer()
    kw = dict(top_k=2, num_experts=8, use_bias=True, norm_topk=True, scale=1.0)
    whole, load, _ = ref.moe_ffn(p, u, "float32", 0, held=(0, 8), **kw)
    parts = [_part(p, u, (first, 2)) for first in range(0, 8, 2)]
    np.testing.assert_allclose(sum(parts), np.asarray(whole[0]), atol=1e-5)
    assert int(np.sum(load)) == 2 * u.shape[1]
    # each share is also what the reference gives for that share
    for first, part in zip(range(0, 8, 2), parts):
        share = {**p, **{n: p[n][first:first + 2] for n in ("w1", "w3", "w2")}}
        want, _, _ = ref.moe_ffn(share, u, "float32", 0, held=(first, 2), **kw)
        np.testing.assert_allclose(part, np.asarray(want[0]), atol=1e-5)
    assert not np.allclose(parts[0], parts[1])


def test_every_token_gets_exactly_top_k_experts_one_of_them_forced():
    cfg = dict(SMALL)
    weights, forced = lm_weights.weights(cfg, 1), 5
    layers = [weights["layers"][i] for i in range(len(cfg["layer_types"]))]
    for layer in layers[cfg["num_dense_layers"]:]:
        layer["ffn"]["bias"] = layer["ffn"]["bias"].at[forced].set(10.0)
    weights = {**weights, "layers": layers}
    params = lm_weights.program_params(cfg, weights)
    toks, frame = _frame(rows=2, seq=48, blocks=1)
    out = _score(cfg, frame, params)
    load = np.asarray(out["expert_load"].values)
    assert (load.sum(-1) == 48 * 2).all()  # none dropped, none doubled
    assert (load[..., forced] == 48).all()  # the forced expert, every token
    assert (load <= 48).all()  # an expert at most once a token
    want_lp, want_load, _ = ref.forward(cfg, weights, toks)
    np.testing.assert_array_equal(load, np.asarray(want_load))
    np.testing.assert_allclose(
        np.asarray(out["token_logprob"].values), np.asarray(want_lp), atol=2e-5
    )


@pytest.mark.parametrize("score", ["sigmoid", "softmax_topk"])
def test_router_weights(score):
    p, u = _moe_layer(seed=2)
    idx, w = moe.route(u[0], p["router"], p["bias"], top_k=3, score=score, scale=2.0)
    assert idx.shape == w.shape == (40, 3) and idx.dtype == jnp.int32
    assert all(len(set(r)) == 3 for r in np.asarray(idx).tolist())
    total = 2.0 if score == "sigmoid" else 1.0
    np.testing.assert_allclose(np.asarray(w).sum(-1), total, rtol=1e-5)


def test_moeffn_is_its_dense_masked_evaluation():
    m = MoEFFN(d_model=16, d_hidden=32, num_experts=8, top_k=2, seed=3)
    x = jnp.asarray(np.random.RandomState(3).randn(24, 16), jnp.float32)
    weights = m._route(m.params, x)  # (N, E), top-2 softmax
    outs = jax.vmap(lambda a, b: jax.nn.gelu(x @ a) @ b)(
        m.params["w1"], m.params["w2"]
    )
    dense = jnp.einsum("ne,end->nd", weights, outs)
    np.testing.assert_allclose(m.apply(m.params, x), dense, rtol=2e-5, atol=1e-6)


class _Counting:
    """A scoring function that counts its traces."""

    def __init__(self, cfg):
        self.inner, self.traces = lm.scoring_fn(cfg, interpret=True), 0

    def __call__(self, tokens, params):
        self.traces += 1
        return self.inner(tokens, params)


def test_second_call_traces_nothing_and_moves_no_bound_byte():
    cfg = dict(SMALL)
    params = lm.init_params(cfg, 0)
    _, frame = _frame(rows=8, seq=16, blocks=4)
    fn, ex = _Counting(cfg), Executor()
    first = tfs.map_blocks(fn, frame, bindings={"params": params}, executor=ex)
    traced = fn.traces
    moved = tele.flat_counters()["bindings.bytes_placed"]
    size = sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
    # the 4 blocks went to 4 devices: the weights were copied to 3 of them
    assert moved == 3 * size and traced >= 1
    assert ex.cache_misses == 1
    second = tfs.map_blocks(fn, frame, bindings={"params": params}, executor=ex)
    assert fn.traces == traced and ex.cache_misses == 1 and ex.cache_hits == 1
    assert tele.flat_counters()["bindings.bytes_placed"] == moved
    np.testing.assert_array_equal(
        np.asarray(first["token_logprob"].values),
        np.asarray(second["token_logprob"].values),
    )
    assert tele.flat_counters()["bindings.leaves"] == 2 * len(
        jax.tree_util.tree_leaves(params)
    )


def test_a_bound_leaf_keeps_its_buffer():
    d0, d1 = jax.devices()[:2]
    tree = {"w": jax.device_put(jnp.arange(8.0), d0), "n": np.arange(3)}
    placed = rb.place({"p": tree}, [d0, d1])["p"]
    on0, on1 = placed.on(d0), placed.on(d1)
    assert on0["w"] is tree["w"]  # where it lives: the array itself
    assert on1["w"].devices() == {d1}
    again = rb.place({"p": tree}, [d0, d1])["p"].on(d1)
    assert again["w"] is on1["w"]  # the one copy, found again
    moved = tele.flat_counters()["bindings.bytes_placed"]
    assert moved == tree["w"].nbytes + 4 * tree["n"].nbytes  # host leaf: each call
    # unscheduled: nothing is copied
    assert rb.place({"p": tree}, None)["p"].on()["w"] is tree["w"]
    del tree, placed, on0, on1, again
    import gc

    gc.collect()
    assert not rb._copies  # the copy went with its leaf


def test_a_block_moved_to_an_unplanned_device_gets_one_counted_copy():
    d0, d1 = jax.devices()[:2]
    tree = {"w": jax.device_put(jnp.arange(8.0), d0)}
    placed = rb.place({"p": tree}, [d0])["p"]
    assert tele.flat_counters()["bindings.bytes_placed"] == 0
    moved = placed.on(d1)  # a failover: d1 was not in the plan
    assert moved["w"].devices() == {d1}
    assert tele.flat_counters()["bindings.bytes_placed"] == tree["w"].nbytes
    assert placed.on(d1)["w"] is moved["w"]
    assert rb.place({"p": tree}, [d1])["p"].on(d1)["w"] is moved["w"]
    assert tele.flat_counters()["bindings.bytes_placed"] == tree["w"].nbytes


def test_a_device_binding_of_a_graph_stays_on_the_device():
    x = np.arange(12, dtype=np.float32)
    df = tfs.TensorFrame([tfs.Column("x", x)], [0, 12])
    from tensorframes_tpu.schema import ScalarType, Shape

    c = tfs.dsl.placeholder(ScalarType.float32, Shape(()), name="c")
    z = (tfs.block(df, "x") + c).named("z")
    bound = jnp.float32(3.0)
    out = tfs.map_blocks(z, df, bindings={"c": bound}, devices=[jax.devices()[0]])
    np.testing.assert_array_equal(np.asarray(out["z"].values), x + 3)
    assert tele.flat_counters()["bindings.bytes_placed"] == 0


def _span_names(run):
    run()
    tele.reset()
    run()
    return {s.name for s in tele.spans()}


@pytest.mark.parametrize("verb", ["map_blocks", "map_rows"])
def test_function_front_end_opens_the_graph_front_ends_spans(verb):
    x = jax.device_put(np.arange(40, dtype=np.float32))
    df = tfs.TensorFrame([tfs.Column("x", x)], [0, 10, 20, 30, 40])
    ph = (tfs.block if verb == "map_blocks" else tfs.row)(df, "x")
    graph = (ph * 2.0).named("z")
    fn = lambda x: {"z": x * 2.0}
    # devices=: both front ends block by block (left to the scheduler, a
    # row-local graph's equal blocks are one group on the column's device)
    run = functools.partial(getattr(tfs, verb), devices=jax.local_devices()[:4])
    ex = Executor()
    of_graph = _span_names(lambda: run(graph, df, executor=ex))
    of_fn = _span_names(lambda: run(fn, df, executor=ex))
    only_a_graph_has, only_a_function_has = {"graph.analyze", "shape.classify"}, set()
    if verb == "map_blocks":
        # a plain function cannot be shown row-local: its blocks are cut
        # at their exact shapes, not taken as padded windows
        only_a_graph_has |= {"shape.pad", "shape.unpad"}
        only_a_function_has = {"frame.cut"}
    assert of_fn == (of_graph - only_a_graph_has) | only_a_function_has
    assert {f"{verb}.plan", "executor.lookup", "scheduler.plan", "frame.match",
            f"{verb}.blocks", f"{verb}.block", "frame.concat"} <= of_fn
    with_bound = _span_names(lambda: run(
        lambda x, b: {"z": x * b}, df, bindings={"b": np.float32(2)}, executor=ex
    ))
    assert "bindings.place" in with_bound
    spans = {s.span_id: s for s in tele.spans()}
    (place,) = [s for s in spans.values() if s.name == "bindings.place"]
    assert spans[place.parent_id].name == f"{verb}.plan"


def test_fn_program_is_the_functions_identity():
    class Model:
        def score(self, x):
            return {"z": x}

    a, b = Model(), Model()
    fp = lambda f: FnProgram(f).fingerprint()
    assert fp(a.score) == fp(a.score) != fp(b.score)
    assert fp(len) == fp(len)
    df = tfs.TensorFrame([tfs.Column("x", np.arange(4.0))], [0, 4])
    ex = Executor()
    for _ in range(3):
        tfs.map_blocks(a.score, df, executor=ex)
    assert ex.cache_misses == 1 and ex.cache_hits == 2
    tfs.map_blocks(b.score, df, executor=ex)
    assert ex.cache_misses == 2


def test_map_rows_fn_takes_the_shape_policy():
    x = jax.device_put(np.arange(36, dtype=np.float32).reshape(18, 2))
    df = tfs.TensorFrame([tfs.Column("x", x)], [0, 9, 18])
    out = tfs.map_rows(lambda x: {"z": x.sum()}, df, executor=Executor())
    np.testing.assert_array_equal(
        np.asarray(out["z"].values), np.asarray(x).sum(-1)
    )
    counters = tele.flat_counters()
    assert counters["shape_bucketing.pad_rows"] == 2 * (16 - 9)


@pytest.mark.parametrize("seq", [64, 2100])
@pytest.mark.parametrize("name", ["lfm2-8b-a1b", "joyai-llm-flash", "nemotron-3-super-120b-a12b",
                                  "hy4-preview", "trinity-mini"])
def test_the_attention_kernels_block_classes_are_counted(name, seq):
    # `lm.score` books, from the frame's shape before the dispatch, the
    # (query block, key block) pairs the attention kernels compute without
    # and with the positional mask: `block_classes` at each layer's kernel
    # block x heads x layers x rows, over the full, sliding, latent and
    # sparse layers of each family's small preset. The counters read no
    # output, so a stand-in function keeps 2,100-token rows cheap
    import json

    from perf.runners.map_blocks_lm import model_config
    from tensorframes_tpu.ops import pallas_kernels

    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        cfg = lm.family_keys(model_config(json.load(f), True))
    blocks = {"full_attention": min(512, seq), "latent_attention": min(1024, seq),
              "sliding_attention": min(1024, seq), "sparse_attention": min(512, seq)}
    want = np.zeros(2)
    for op in lm.layer_plan(cfg)[:, 0]:
        kind = lm.OPS[op]
        if kind in blocks:
            window = cfg["sliding_window"] if kind == "sliding_attention" else None
            want += pallas_kernels.block_classes(seq, blocks[kind], blocks[kind], window)
    assert want[1] > 0 and (seq == 64 or want[0] > 0)
    rows = 2
    frame = tfs.TensorFrame([tfs.Column("tokens", jnp.zeros((rows, seq), jnp.int32))], [0, rows])
    params = {"moe": {"w_up": np.zeros((1, int(cfg["num_experts"]), 1), np.float32)}}
    before = dict(tele.flat_counters())
    lm.score(lambda tokens, params: {"token_logprob": tokens.astype(jnp.float32)},
             frame, params, cfg)
    counters = tele.flat_counters()
    got = [counters[k] - before.get(k, 0)
           for k in ("lm.attention_inner_blocks", "lm.attention_edge_blocks")]
    np.testing.assert_array_equal(got, want * int(cfg["num_attention_heads"]) * rows)
    model = tfs.diagnostics(format="json")["model"]
    assert model["lm.attention_inner_blocks"] == counters["lm.attention_inner_blocks"]
    assert model["lm.attention_edge_blocks"] == counters["lm.attention_edge_blocks"]
