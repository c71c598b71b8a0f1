"""`correct` comes out false when the timed path is broken underneath,
and when the control (the next precision down) stands in the program's
place. Drives the harness's `measure` and each runner's `check` past the
look for a chip, on the CPU at the rehearsal sizes."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.lib import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def drive(cell_name, seed, break_path=None, calls_seconds=0.3):
    """One rehearsal-sized run in this process; `break_path(tfs)` may
    plant a fault in the package before the window and returns the undo."""
    _, cell, config, traffic = harness.load_cell(ROOT, cell_name)
    traffic = {**traffic, **traffic["rehearse"]}
    env = harness.make_env(ROOT, cell, config, traffic, seed, rehearse=True)
    runner = harness.make_runner(env)
    tfs = env.tfs
    undo = break_path(tfs) if break_path else (lambda: None)
    try:
        got = harness.measure(env, runner, calls_seconds)
    finally:
        undo()
    compared, wrong = runner.check()
    return harness.decide(compared, got.summary["raised"]), compared, wrong, got


def patched(name, make):
    def plant(tfs):
        real = getattr(tfs, name)
        setattr(tfs, name, make(real, tfs))
        return lambda: setattr(tfs, name, real)
    return plant


def state_unchanged(real, tfs):
    """The step returns its state as it got it (after a few sound calls)."""
    from tensorframes_tpu.frame import Column
    n = [0]

    def verb(fetch, frame, *a, **k):
        n[0] += 1
        if n[0] < 6:
            return real(fetch, frame, *a, **k)
        return tfs.TensorFrame([Column("z", frame["x"].values)], frame.offsets)
    return verb


def half_left_out(real, tfs):
    """The second half of the rows is not computed: passed through."""
    import jax.numpy as jnp
    from tensorframes_tpu.frame import Column

    def verb(fetch, frame, *a, **k):
        out = real(fetch, frame, *a, **k)
        name = [c for c in out.columns if c not in frame.columns][0]
        src = frame[frame.columns[0]].values
        v = out[name].values
        half = v.shape[0] // 2
        stale = src[half:] if src.shape == v.shape else jnp.zeros_like(v[half:])
        return tfs.TensorFrame([Column(name, jnp.concatenate([v[:half], stale]))],
                               frame.offsets)
    return verb


def one_answer_altered(real, tfs):
    """One value of one call's output is off by one unit in the last
    place where it is produced."""
    import jax.numpy as jnp
    from tensorframes_tpu.frame import Column
    n = [0]

    def verb(fetch, frame, *a, **k):
        out = real(fetch, frame, *a, **k)
        n[0] += 1
        name = [c for c in out.columns if c not in frame.columns][0]
        v = out[name].values
        if n[0] < 5:  # every later call: the kept outputs hold one
            return out
        last = (v.shape[0] - 1,) + (0,) * (v.ndim - 1)
        bumped = v.at[last].set(jnp.nextafter(v[last] + 1e-3, jnp.inf).astype(v.dtype))
        return tfs.TensorFrame([Column(name, bumped)], frame.offsets)
    return verb


FAULTS = {"state_unchanged": state_unchanged, "half_left_out": half_left_out,
          "one_answer_altered": one_answer_altered}
VERB = {"map_chain": "map_blocks", "map_rows_mlp": "map_rows"}
CELLS = [(w["name"], w["config"]) for w in BENCH["workloads"]]


def runner_of(config_name):
    entry = {c["name"]: c for c in BENCH["configs"]}[config_name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)["runner"]


@pytest.mark.parametrize("cell,config", CELLS)
def test_sound_run_is_correct(cell, config):
    ok, compared, wrong, got = drive(cell, seed=2147483659)
    assert ok and wrong == 0 and got.summary["attempted"] > 0, compared


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell,config", CELLS)
def test_fault_is_not_correct(cell, config, fault):
    runner = runner_of(config)
    if runner == "map_rows_mlp" and fault == "state_unchanged":
        pytest.skip("scoring keeps no state from call to call")
    ok, compared, wrong, _ = drive(
        cell, seed=77, break_path=patched(VERB[runner], FAULTS[fault])
    )
    assert not ok and wrong > 0, compared


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
def test_control_map_in_bfloat16_is_not_correct(seed):
    """The control of verbs-dense-f32: the reference's x + add computed in
    bfloat16, the precision below float32, in the program's place."""
    import jax.numpy as jnp

    _, _, config, traffic = harness.load_cell(ROOT, "map_chain_1block")
    ref = harness.load_reference(ROOT, config)
    rows = traffic["rehearse"]["rows"]
    from perf.lib import datagen
    x = jnp.asarray(datagen.rows_on_host(config["input"], np.arange(rows), 1, seed))
    sound = x + jnp.float32(config["add"])
    low = (x.astype(jnp.bfloat16) + jnp.bfloat16(config["add"])).astype(jnp.float32)
    assert ref.compare(sound, rows, seed, config) == (0, 0.0)
    bad, worst = ref.compare(low, rows, seed, config)
    assert bad > rows // 2 and worst >= 1.0


@pytest.mark.parametrize("seed", [5, 2147483659, 3000000019])
def test_control_mlp_three_pass_is_not_correct(seed):
    """The control of mlp-512-scoring: the reference with each matmul in
    three bfloat16 passes (`high`), the precision below `highest`, against
    the limit; the float32 reference passes it."""
    _, _, config, _ = harness.load_cell(ROOT, "mlp_rows_1m")
    ref = harness.load_reference(ROOT, config)
    from perf.lib import datagen
    sizes = config["layer_sizes"]
    params = ref.make_params(sizes, seed)
    x = datagen.rows_on_host(config["input"], np.arange(2048), sizes[0], seed)
    want = ref.forward(x, params)
    limit = config["limits"]["probs_max_abs_err"]
    f32 = np.max(np.abs(ref.forward(x, params, dtype=np.float32) - want))
    low = np.max(np.abs(ref.forward_three_pass(x, params) - want))
    assert f32 <= limit < low, (f32, limit, low)
