"""`hy4_score_16k` at its rehearsal sizes on the CPU, `test_lm_cell.py`'s
checks for the sparse-attention runner: the run as the driver starts it, a
sound run, the control one step down in precision, and four faults planted
in the timed path (dense attention in place of the selection, a shared
layer that reselects, a plain residual in place of the hyper-connections,
a held expert left out), each of which must come out not correct; the
spans a call opens; the work counts pinned to the published model and to
this chip's share; the four new metric readers. `test_rehearse.py` and
`test_names.py` cover the cell too (they read every cell of
BENCHMARK.json)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lm_sparse_plants  # noqa: E402
from lm_sparse_plants import FAULTS  # noqa: E402
from perf.lib import harness  # noqa: E402

CELL = "hy4_score_16k"
NUMBERS = {"logprob_p99_abs_err", "routing_swapped_share", "index_swapped_share",
           "expert_load_l1_share"}


def make(seed):
    _, cell, config, traffic = harness.load_cell(ROOT, CELL)
    traffic = {**traffic, **traffic["rehearse"]}
    env = harness.make_env(ROOT, cell, config, traffic, seed, rehearse=True)
    return env, harness.make_runner(env)


def drive(seed, plant=None):
    env, runner = make(seed)
    if plant:
        plant(env, runner)
    got = harness.measure(env, runner, 0.3)
    compared, wrong = runner.check()
    return harness.decide(compared, got.summary["raised"]), compared, wrong, got


def test_rehearsal_line_has_the_four_comparisons():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", CELL, "--seed", "3000000019",
         "--seconds", "0.5", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["metrics"] == {}
    assert set(line["compared"]) == NUMBERS
    assert line["compiles_in_window"] == 0


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
def test_sound_run_is_correct_and_moves_no_bound_byte(seed):
    ok, compared, wrong, got = drive(seed)
    assert ok and wrong == 0 and got.summary["attempted"] > 0, compared
    calls = got.summary["attempted"]
    c = got.counters
    assert c["bindings.bytes_placed"] == 0
    assert c["lm.tokens"] == calls * 2 * 64
    # five sparse layers of 4 heads, indexers full, full, shared x 3 (2
    # heads), top-16 of 64; four expert layers top-4, half of 16 held
    kept = 16 * 17 // 2 + 48 * 16
    assert c["lm.dsa_selected_pairs"] == calls * 2 * kept * 4 * 5
    assert c["lm.dsa_index_pairs"] == calls * 2 * (64 * 65 // 2) * 2 * 2
    assert c["lm.index_reuses"] == calls * 2 * 3
    assert c["lm.hc_stream_bytes"] == c["lm.tokens"] * 4 * 64 * 4 * 2 * 5
    assert c["moe.routed_rows"] == c["lm.tokens"] * 4 * 4
    assert c["moe.held_rows_expected"] == c["moe.routed_rows"] / 2


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    ok, compared, wrong, _ = drive(77, FAULTS[fault])
    assert not ok and wrong > 0, compared


@pytest.mark.parametrize("seed", [5, 2147483659])
@pytest.mark.parametrize("step", sorted(lm_sparse_plants.CONTROLS["float32"]))
def test_control_in_lower_precision_is_not_correct(seed, step):
    """The reference computed below the preset's float32 stands in the
    program's place and goes through the runner's check and the harness's
    decision: with bfloat16 operands, and with the sums of the indexer, the
    attention and the held experts kept in bfloat16 as well."""
    env, runner = make(seed)
    assert runner.model["dtype"] == "float32"
    compared, wrong = lm_sparse_plants.control(
        runner, lm_sparse_plants.CONTROLS["float32"][step])
    assert wrong == 1 and not harness.decide(compared, 0), compared
    assert compared["expert_load_l1_share"]["value"] == 0  # its own routing's counts


def test_the_reference_in_its_own_place_reads_zero():
    env, runner = make(5)
    own = runner.reference_rows(runner.check_rows, operands=runner.model["dtype"])
    compared, wrong = lm_sparse_plants.judge_in_the_programs_place(runner, own)
    assert wrong == 0 and all(c["value"] == 0 for c in compared.values()), compared


def test_the_program_and_the_reference_are_given_the_share():
    env, runner = make(5)
    assert env.config["n_routed_experts"] == 8 and env.config["router_width"] == 256
    assert tuple(env.config["held_experts"]) == (0, 8)
    assert runner.model["n_routed_experts"] == 16 and runner.held == (0, 8)
    moe = runner.program_params["moe"]
    assert moe["w_up"].shape == (4, 8, 64, 64) and moe["router"].shape == (4, 64, 16)
    from tensorframes_tpu.models import lm

    from perf.runners.map_blocks_lm_hybrid import model_config

    full, held = model_config(env.config, False)
    keys = lm.family_keys(full)
    assert held == (0, 8) and keys["num_experts"] == 256 and keys["num_dense_layers"] == 1
    assert keys["layer_types"] == ["sparse_attention"] * 5 and keys["rope_theta"] == 1e7
    assert not keys.get("use_expert_bias") and keys["swiglu_limit"] == 10


def test_the_configuration_keeps_the_published_widths():
    """Every number of the catalog's config as published but the keys
    `reduced` lists (and their published values stated beside them)."""
    _, _, config, _ = harness.load_cell(ROOT, CELL)
    assert config["reduced"] == [
        "num_hidden_layers", "mlp_layer_types", "layer_types", "indexer_types",
        "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    for key, value in (("hidden_size", 6144), ("num_attention_heads", 64),
                       ("q_lora_rank", 2048), ("kv_lora_rank", 512),
                       ("qk_nope_head_dim", 192), ("qk_rope_head_dim", 64),
                       ("v_head_dim", 256), ("index_n_heads", 32), ("index_head_dim", 128),
                       ("index_topk", 2048), ("hc_mult", 4), ("moe_intermediate_size", 2048),
                       ("intermediate_size", 18432), ("num_experts_per_tok", 8),
                       ("routed_scaling_factor", 2.827), ("swiglu_limit", 10)):
        assert config[key] == value, key
    assert config["indexer_types"] == ["full", "full", "shared", "shared", "shared"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (config["n_routed_experts"], config["vocab_size"]) == (8, 15104)
    assert config["published"]["n_routed_experts"] == 256
    assert config["published"]["vocab_size"] == 120832


def test_span_readers_over_the_rehearsal(tmp_path, capsys):
    """`test_span_reduce.py`'s check of the five `program_span` readers,
    for this cell: the function front end opens the spans they read."""
    import importlib
    import types

    readers = ["plan_host_ms_per_call", "pad_host_ms_per_call",
               "dispatch_host_ms_per_call", "cut_concat_host_ms_per_call",
               "verb_unattributed_pct"]
    env, runner = make(2147483659)
    got = harness.measure(env, runner, 0.3, str(tmp_path / "trace"), 0.3)
    ctx = types.SimpleNamespace(
        traced_calls=[c for c in got.traced_calls if c.error is None])
    values = {n: importlib.import_module("perf.metrics." + n).read(ctx) for n in readers}
    assert all(isinstance(v, float) for v in values.values()), values
    read = ctx.spans_per_call
    parts = sum(v for k, v in values.items() if k.endswith("_host_ms_per_call"))
    own = values["verb_unattributed_pct"] / 100.0 * read["verb_ms"]
    assert parts + own == pytest.approx(read["verb_ms"], rel=1e-6)
    assert read["by_name"]["map_blocks.block"]["per_call"] == 2
    assert read["by_name"]["bindings.place"]["per_call"] == 1
    assert values["pad_host_ms_per_call"] == 0.0  # exact shapes: no pad
    capsys.readouterr()


def test_work_counts_this_chips_share_of_the_published_model():
    from perf.lib import work_map_blocks_lm_sparse as work

    _, _, config, traffic = harness.load_cell(ROOT, CELL)
    per_token = work.flops_per_token(config)
    assert abs(per_token - 4724.85e6) < 1e6  # ISSUE 40's arithmetic: 4,728 M
    assert work.selected_per_query(config) == pytest.approx(1920.0625)
    share = lambda x: x / per_token
    assert 0.348 < share(5 * 2.0 * (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448
                                    + 64 * 256 * 6144)) < 0.350  # MLA projections
    assert 0.212 < share(5 * 2.0 * 6144 * 64 * 256) < 0.214  # the gate
    assert 0.132 < share(5 * work.attention_core_flops_per_token(config)) < 0.134
    assert 0.143 < share(3 * 2.0 * 6144 * 18432) < 0.145  # the dense layer
    assert 0.082 < share(4 * work.moe_flops_per_token(config)) < 0.084
    assert 0.039 < share(2.0 * 6144 * 15104) < 0.040  # the head, over the slice
    # 8 experts a token, 8 of 256 held here: a quarter of a row of 3 x 2 x 6144 x 2048
    assert work.expert_flops_per_token(config) == 0.25 * 6 * 6144 * 2048
    assert work.index_flops(config, 1) == 16384 * 16385 / 2 * 32 * 256 * 2
    assert work.attention_flops(config, 1) == pytest.approx(
        16384 * 1920.0625 * 5 * 64 * 2 * 512)
    assert work.work(config)["flops_per_row"] == traffic["seq"] * per_token


def test_metric_readers_find_nothing_without_the_program():
    import types

    from perf.metrics import (dsa_attention_device_pct, dsa_attention_roofline,
                              dsa_index_device_pct, dsa_index_roofline)

    readers = (dsa_attention_device_pct, dsa_attention_roofline, dsa_index_device_pct,
               dsa_index_roofline)
    ctx = types.SimpleNamespace(
        config={}, counters={}, window={"rows": 4}, rows_per_call=2,
        trace={"device_ops": [["fusion f32[8]", 1.0]], "program_seconds": 4.0},
        traced_calls=[1], chips=1,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )
    assert all(r.read(ctx) is None for r in readers)
    _, _, config, _ = harness.load_cell(ROOT, CELL)
    ctx.config = config
    assert all(r.read(ctx) is None for r in readers)  # the patterns, no such operation
    ctx.trace["device_ops"] += [["lm.dsa_index.4 f32[1,1024,16384]", 0.2],
                                ["lm.dsa.7 bf16[1,64,16384,256]", 1.0],
                                ["lm.mla.2 bf16[1,32,32768,128]", 0.3]]
    assert dsa_index_device_pct.read(ctx) == pytest.approx(5.0)
    assert dsa_attention_device_pct.read(ctx) == pytest.approx(25.0)
    index = 2 * 16384 * 16385 / 2 * 32 * 256 * 2
    assert dsa_index_roofline.read(ctx) == pytest.approx(100 * index / 197e12 / 0.2)
    core = 2 * 16384 * 1920.0625 * 5 * 64 * 1024
    assert dsa_attention_roofline.read(ctx) == pytest.approx(100 * core / 197e12 / 1.0)
