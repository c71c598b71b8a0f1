"""`joyai_score_32k` at its rehearsal sizes on the CPU, `test_lm_cell.py`'s
checks for the latent-attention runner: the run as the driver starts it, a
sound run, the control one step down in precision, and three faults
planted in the timed path, each of which must come out not correct; the
spans a call opens; the work counts pinned to the published model; the
two new metric readers. `test_rehearse.py` and `test_names.py` cover the
cell too (they read every cell of BENCHMARK.json)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lm_latent_plants  # noqa: E402
from lm_latent_plants import FAULTS  # noqa: E402
from perf.lib import harness  # noqa: E402

CELL = "joyai_score_32k"


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
    assert got.counters["moe.routed_rows"] == got.counters["lm.tokens"] * 2 * 4
    # causal pairs x heads x attention layers, of 2 rows of 64 positions
    assert got.counters["lm.attention_pairs"] == calls * 2 * (64 * 65 // 2) * 4 * 3


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    ok, compared, wrong, _ = drive(77, FAULTS[fault])
    assert not ok and wrong > 0, compared


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
@pytest.mark.parametrize("step", sorted(lm_latent_plants.CONTROLS["float32"]))
def test_control_in_lower_precision_is_not_correct(seed, step):
    """The reference computed below the preset's float32 stands in the
    program's place and goes through the runner's check and the harness's
    decision: with bfloat16 operands, and with the sums of the expert
    matmuls and of the attention's products kept in bfloat16 as well."""
    env, runner = make(seed)
    assert runner.model["dtype"] == "float32"
    compared, wrong = lm_latent_plants.control(
        runner, lm_latent_plants.CONTROLS["float32"][step])
    assert wrong == 1 and not harness.decide(compared, 0), compared
    assert compared["expert_load_l1_share"]["value"] == 0  # its own routing's counts


def test_the_reference_in_its_own_place_reads_zero():
    env, runner = make(5)
    own = runner.reference_rows(runner.check_rows, operands=runner.model["dtype"])
    compared, wrong = lm_latent_plants.judge_in_the_programs_place(runner, own)
    assert wrong == 0 and all(c["value"] == 0 for c in compared.values()), compared


def test_the_program_and_the_reference_read_the_published_names():
    """The file's `derived` keys are for the other family's metric readers:
    the runner takes them off, so the program's own normalising function and
    the reference see what the family publishes."""
    env, runner = make(5)
    assert not set(env.config["derived"]) & set(runner.model)
    assert runner.model["n_routed_experts"] == 16 and "num_experts" not in runner.model
    from tensorframes_tpu.models import lm

    full = {k: v for k, v in env.config.items() if k not in env.config["derived"]}
    keys = lm.family_keys(full)
    assert keys["layer_types"] == env.config["layer_types"]
    assert keys["num_dense_layers"] == env.config["num_dense_layers"]
    assert keys["num_experts"] == env.config["num_experts"]
    assert keys["norm_eps"] == 1e-6 and keys["use_expert_bias"] is True


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
    from perf.lib import work_map_blocks_lm as old
    from perf.lib import work_map_blocks_lm_latent as work

    _, _, config, traffic = harness.load_cell(ROOT, CELL)
    per_token = work.flops_per_token(config)
    assert abs(per_token - 2.9027e9) < 1e6  # ISSUE 33's arithmetic: 2,902.7 M
    core = 5 * work.attention_flops_per_token(config)
    assert work.attention_flops(config, 1) == core == 5 * (192 + 128) * 32 * 32768
    assert 0.57 < core / per_token < 0.585
    assert 0.66 < (core + 5 * work.projection_flops_per_token(config)) / per_token < 0.675
    assert 0.10 < 4 * work.expert_flops_per_token(config) / per_token < 0.11
    assert work.work(config)["flops_per_row"] == traffic["seq"] * per_token
    # the other family's reader, unedited, counts this cell's grouped
    # matmuls off the file's derived keys (the shared expert is a plain
    # matmul and is not in that kernel's count)
    assert old.expert_flops(config, 1) == 4 * 8 * 3 * 2 * 2048 * 768
    assert old.expert_flops(config, 1) == 4 * work.expert_flops_per_token(config)


def test_metric_readers_find_nothing_without_the_program():
    import types

    from perf.metrics import (bound_bytes_moved_per_call, mla_attention_device_pct,
                              mla_attention_roofline, moe_expert_device_pct,
                              moe_expert_roofline)

    ctx = types.SimpleNamespace(
        config={}, counters={}, window={"rows": 4}, rows_per_call=2,
        trace={"device_ops": [["fusion f32[8]", 1.0]], "program_seconds": 4.0},
        traced_calls=[1], chips=1, peaks={"bf16_flops_per_s": 197e12},
    )
    for reader in (bound_bytes_moved_per_call, moe_expert_roofline,
                   moe_expert_device_pct, mla_attention_roofline,
                   mla_attention_device_pct):
        assert reader.read(ctx) is None
    _, _, config, _ = harness.load_cell(ROOT, CELL)
    ctx.config = config
    assert mla_attention_roofline.read(ctx) is None  # the pattern, but no such operation
    assert mla_attention_device_pct.read(ctx) is None
    ctx.trace["device_ops"] += [["lm.mla.7 bf16[1,32,32768,128]", 2.0],
                                ["ragged-dot-none f32[65536,1536]", 0.5],
                                ["ragged-dot-none.1 f32[65536,2048]", 0.25],
                                ["ragged-dot-metadata (s32[1025], ...)", 0.1],
                                ["fusion.9 f32[1,32768,2048]", 0.3]]
    assert mla_attention_device_pct.read(ctx) == 50.0
    least = 2 * 32768 * 5 * (192 + 128) * 32 * 32768 / 197e12
    assert mla_attention_roofline.read(ctx) == pytest.approx(100 * least / 2.0)
    assert moe_expert_device_pct.read(ctx) == 18.75
    least = 2 * 32768 * 4 * 8 * 3 * 2 * 2048 * 768 / 197e12
    assert moe_expert_roofline.read(ctx) == pytest.approx(100 * least / 0.75)
