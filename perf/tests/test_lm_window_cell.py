"""`trinity_score_32k` at its rehearsal sizes on the CPU, `test_lm_cell.py`'s
checks for the sliding-window runner: the run as `perf/run.py` starts it, a
sound run with the window's counters, the controls one step down in
precision, the program's window one key wider, and faults planted in the
timed path (a state handed back unchanged, half the rows left out, one
answer altered, a held expert left out), each of which must come out not
correct; the spans a call opens; the work counts pinned to the published
model; the two new metric readers. `test_rehearse.py` and `test_names.py`
cover the cell too (they read every cell of BENCHMARK.json)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lm_window_plants  # noqa: E402
from lm_window_plants import BROKEN, FAULTS  # noqa: E402
from perf.lib import harness  # noqa: E402

CELL = "trinity_score_32k"
NUMBERS = {"logprob_p99_abs_err", "routing_swapped_share", "expert_load_l1_share"}


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


def test_rehearsal_line_has_the_three_comparisons():
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
    # four sliding layers of 4 heads, a window of 16 in 64; one full layer
    kept = 16 * 17 // 2 + 48 * 16
    assert c["lm.swa_pairs"] == calls * 2 * kept * 4 * 4
    assert c["lm.swa_blocks"] == calls * 2 * 1 * 4 * 4
    assert c["lm.attention_pairs"] == calls * 2 * (64 * 65 // 2) * 4
    assert c["moe.routed_rows"] == c["lm.tokens"] * 2 * 4
    assert c["moe.held_rows_expected"] == c["moe.routed_rows"]


@pytest.mark.parametrize("fault", sorted({**FAULTS, **BROKEN}))
def test_planted_fault_is_not_correct(fault):
    ok, compared, wrong, _ = drive(77, {**FAULTS, **BROKEN}[fault])
    assert not ok and wrong > 0, compared


@pytest.mark.parametrize("seed", [5, 2147483659])
@pytest.mark.parametrize("step", sorted(lm_window_plants.CONTROLS["float32"]))
def test_control_in_lower_precision_is_not_correct(seed, step):
    """The reference computed below the preset's float32 stands in the
    program's place and goes through the runner's check and the harness's
    decision: with bfloat16 operands, and with the held experts' sums kept
    in bfloat16 as well."""
    env, runner = make(seed)
    assert runner.model["dtype"] == "float32"
    compared, wrong = lm_window_plants.control(
        runner, lm_window_plants.CONTROLS["float32"][step])
    assert wrong == 1 and not harness.decide(compared, 0), compared
    assert compared["expert_load_l1_share"]["value"] == 0  # its own routing's counts


def test_the_reference_in_its_own_place_reads_zero():
    env, runner = make(5)
    own = runner.reference_rows(runner.check_rows, operands=runner.model["dtype"])
    compared, wrong = lm_window_plants.judge_in_the_programs_place(runner, own)
    assert wrong == 0 and all(c["value"] == 0 for c in compared.values()), compared


def test_the_configuration_keeps_the_published_widths():
    """Every number of the catalog's config as published but the keys
    `reduced` lists (and their published values stated beside them)."""
    _, _, config, _ = harness.load_cell(ROOT, CELL)
    assert config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types", "global_attn_every_n_layers"]
    for key, value in (("hidden_size", 2048), ("num_attention_heads", 32),
                       ("num_key_value_heads", 4), ("head_dim", 128),
                       ("intermediate_size", 6144), ("moe_intermediate_size", 1024),
                       ("num_experts", 128), ("num_experts_per_tok", 8),
                       ("num_shared_experts", 1), ("route_scale", 2.826),
                       ("sliding_window", 2048), ("vocab_size", 200192),
                       ("rope_theta", 10000), ("mup_enabled", True), ("model_type", "afmoe")):
        assert config[key] == value, key
    assert config["layer_types"] == ["sliding_attention"] * 2 + ["full_attention"] + [
        "sliding_attention"] * 2
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    assert config["published"]["num_hidden_layers"] == 32
    assert config["published"]["global_attn_every_n_layers"] == 4


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


def test_work_counts_the_published_model():
    from perf.lib import work_map_blocks_lm as lm_work
    from perf.lib import work_map_blocks_lm_window as work

    _, _, config, traffic = harness.load_cell(ROOT, CELL)
    per_token = work.flops_per_token(config)
    assert abs(per_token - 2021.66e6) < 1e5  # 2.02 GFLOP a token
    assert work.window_keys(config) / 32768 == pytest.approx(1984.03125)
    share = lambda x: x / per_token
    assert 0.134 < share(5 * work.projection_flops_per_token(config)) < 0.136  # 272.6 M
    assert 0.132 < share(work.core_flops(config, 32768 * 32769 / 2) / 32768) < 0.134
    assert 0.063 < share(work.window_flops(config, 1) / 32768) < 0.065  # 130.0 M
    assert 0.224 < share(4 * work.moe_flops_per_token(config)) < 0.226  # 455.3 M
    assert 0.405 < share(2.0 * 2048 * 200192) < 0.407  # the head
    assert work.window_flops(config, 1) == 32768 * 1984.03125 * 32 * 4 * 128 * 4
    assert work.work(config)["flops_per_row"] == traffic["seq"] * per_token
    # what `moe_expert_roofline` reads: three matrices of every routed expert
    assert lm_work.expert_flops(config, 1) == 4 * 8 * 3 * 2 * 2048 * 1024


def test_metric_readers_find_nothing_without_the_program():
    import types

    from perf.metrics import swa_attention_device_pct, swa_attention_roofline

    readers = (swa_attention_device_pct, swa_attention_roofline)
    ctx = types.SimpleNamespace(
        config={}, counters={}, window={"rows": 4}, rows_per_call=2,
        trace={"device_ops": [["fusion f32[8]", 1.0]], "program_seconds": 4.0},
        traced_calls=[1], chips=1,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )
    assert all(r.read(ctx) is None for r in readers)
    _, _, config, _ = harness.load_cell(ROOT, CELL)
    ctx.config = config
    assert all(r.read(ctx) is None for r in readers)  # the pattern, no such operation
    ctx.trace["device_ops"] += [["lm.swa.3 bf16[1,32,32768,128]", 0.5],
                                ["lm.attention.2 bf16[1,32,32768,128]", 0.3]]
    assert swa_attention_device_pct.read(ctx) == pytest.approx(12.5)
    core = 2 * 32768 * 1984.03125 * 32 * 4 * 128 * 4
    assert swa_attention_roofline.read(ctx) == pytest.approx(100 * core / 197e12 / 0.5)
