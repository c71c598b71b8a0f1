"""`nemotron3_score_32k` at its rehearsal sizes on the CPU, `test_lm_cell.py`'s
checks for the hybrid state-space runner: the run as the driver starts it,
a sound run, the control one step down in precision, and three faults
planted in the timed path (a held expert left out, the scan's state not
handed over at one chunk boundary, another row's `expert_load`), each of
which must come out not correct; the spans a call opens; the work counts
pinned to the published model and to this chip's share; the two new
metric readers. `test_rehearse.py` and `test_names.py` cover the cell too
(they read every cell of BENCHMARK.json)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lm_hybrid_plants  # noqa: E402
from lm_hybrid_plants import FAULTS  # noqa: E402
from perf.lib import harness  # noqa: E402

CELL = "nemotron3_score_32k"


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
    assert set(line["compared"]) == {
        "logprob_p99_abs_err", "routing_swapped_share", "expert_load_l1_share"}
    assert line["compiles_in_window"] == 0


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
def test_sound_run_is_correct_and_moves_no_bound_byte(seed):
    ok, compared, wrong, got = drive(seed)
    assert ok and wrong == 0 and got.summary["attempted"] > 0, compared
    calls = got.summary["attempted"]
    assert got.counters["bindings.bytes_placed"] == 0
    assert got.counters["lm.tokens"] == calls * 2 * 64
    # MEM*E: two Mamba-2 layers, two expert layers (top-4, a quarter of
    # the 16 experts held), one attention layer of 4 heads
    assert got.counters["lm.ssm_steps"] == got.counters["lm.tokens"] * 2
    assert got.counters["moe.routed_rows"] == got.counters["lm.tokens"] * 2 * 4
    assert got.counters["moe.held_rows_expected"] == got.counters["moe.routed_rows"] / 4
    assert got.counters["lm.attention_pairs"] == calls * 2 * (64 * 65 // 2) * 4 * 1


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    ok, compared, wrong, _ = drive(77, FAULTS[fault])
    assert not ok and wrong > 0, compared


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
@pytest.mark.parametrize("step", sorted(lm_hybrid_plants.CONTROLS["float32"]))
def test_control_in_lower_precision_is_not_correct(seed, step):
    """The reference computed below the preset's float32 stands in the
    program's place and goes through the runner's check and the harness's
    decision: with bfloat16 operands, and with the sums of the expert
    matmuls and the scan's carried state kept in bfloat16 as well."""
    env, runner = make(seed)
    assert runner.model["dtype"] == "float32"
    compared, wrong = lm_hybrid_plants.control(
        runner, lm_hybrid_plants.CONTROLS["float32"][step])
    assert wrong == 1 and not harness.decide(compared, 0), compared
    assert compared["expert_load_l1_share"]["value"] == 0  # its own routing's counts


def test_the_reference_in_its_own_place_reads_zero():
    env, runner = make(5)
    own = runner.reference_rows(runner.check_rows, operands=runner.model["dtype"])
    compared, wrong = lm_hybrid_plants.judge_in_the_programs_place(runner, own)
    assert wrong == 0 and all(c["value"] == 0 for c in compared.values()), compared


def test_the_program_and_the_reference_are_given_the_share():
    """The file counts the experts held here; the runner hands both sides
    the router's published width with `held`, and the held experts' weights
    alone."""
    env, runner = make(5)
    assert env.config["n_routed_experts"] == 128 and env.config["router_width"] == 512
    assert tuple(env.config["held_experts"]) == (0, 128)
    assert runner.model["n_routed_experts"] == 16 and runner.held == (0, 4)
    assert "router_width" not in runner.model and "held_experts" not in runner.model
    moe = runner.program_params["moe"]
    assert moe["w_up"].shape == (2, 4, 32, 48) and moe["router"].shape == (2, 64, 16)
    from tensorframes_tpu.models import lm

    from perf.runners.map_blocks_lm_hybrid import model_config

    full, held = model_config(env.config, False)
    keys = lm.family_keys(full)
    assert held == (0, 128) and keys["num_experts"] == 512
    assert keys["layer_types"] == [lm.PATTERN[ch] for ch in "MEMEMEM*EME"]
    assert keys["norm_eps"] == 1e-5 and keys["use_expert_bias"] is True
    assert keys["ffn_act"] == "relu2" and not keys["rope"] and not keys["qk_norm"]


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
    from perf.lib import work_map_blocks_lm_hybrid as work

    _, _, config, traffic = harness.load_cell(ROOT, CELL)
    per_token = work.flops_per_token(config)
    assert abs(per_token - 2.5788e9) < 1e6  # ISSUE 35's arithmetic: 2,579 M
    assert abs(work.ssm_flops_per_token(config) - 224.5e6) < 1e5
    assert abs(work.moe_flops_per_token(config) - 169.6e6) < 1e5
    assert abs(work.attention_flops_per_token(config) - 339.7e6) < 1e5
    # 22 experts a token, a quarter of them held here: 5.5 rows of 2 x 2 x 1024 x 2688
    assert work.expert_flops_per_token(config) == 5.5 * 4 * 1024 * 2688
    assert 0.43 < 5 * work.ssm_flops_per_token(config) / per_token < 0.44
    assert 0.32 < 5 * work.moe_flops_per_token(config) / per_token < 0.335
    assert work.ssd_flops(config, 1) == 5 * work.ssd_flops_per_token(config)
    assert abs(work.ssd_flops_per_token(config) - 5.374e6) < 1e3
    assert work.ssd_bytes(config, 1) == 5 * (2 * 2 * 8192 + 2 * 2 * 1024 + 4 * 128)
    assert work.work(config)["flops_per_row"] == traffic["seq"] * per_token


def test_metric_readers_find_nothing_without_the_program():
    import types

    from perf.metrics import (bound_bytes_moved_per_call, moe_expert_device_pct,
                              ssd_scan_device_pct, ssd_scan_roofline)

    ctx = types.SimpleNamespace(
        config={}, counters={}, window={"rows": 4}, rows_per_call=2,
        trace={"device_ops": [["fusion f32[8]", 1.0]], "program_seconds": 4.0},
        traced_calls=[1], chips=1,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )
    for reader in (bound_bytes_moved_per_call, moe_expert_device_pct,
                   ssd_scan_roofline, ssd_scan_device_pct):
        assert reader.read(ctx) is None
    _, _, config, _ = harness.load_cell(ROOT, CELL)
    ctx.config = config
    assert ssd_scan_roofline.read(ctx) is None  # the pattern, but no such operation
    assert ssd_scan_device_pct.read(ctx) is None
    ctx.trace["device_ops"] += [["lm.ssd.1 bf16[1,32768,8192]", 0.5],
                                ["ragged-dot-none f32[8192,2688]", 0.5],
                                ["ragged-dot-none.1 f32[8192,1024]", 0.25],
                                ["lm.attention.2 bf16[1,32,32768,128]", 0.3]]
    assert ssd_scan_device_pct.read(ctx) == 12.5
    assert moe_expert_device_pct.read(ctx) == 18.75
    tokens = 2 * 32768
    least = max(tokens * 5 * 5.373952e6 / 197e12, tokens * 5 * 37376 / 819e9)
    assert ssd_scan_roofline.read(ctx) == pytest.approx(100 * least / 0.5)
