"""`map_chain_200blocks_4chips` at the rehearsal sizes on four virtual CPU
devices, in a process of its own (the devices are fixed when jax starts):
the route its counters show, the three readers of the scheduler's books
by arithmetic, `None` from each where the program has no such counter,
and `test_faults.py`'s three planted faults, which come out not correct
here too. `four_chips_route.py` is what runs; the same script reads the
counters on the chips."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def route():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "four_chips_route.py"),
         "--rehearse", "--faults", "--seed", "2147483659"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_route_is_a_window_a_block_on_four_devices(route):
    c, calls, blocks = route["counters"], route["calls"], route["blocks"]
    assert route["correct"] and route["raised"] == 0 and calls > 0
    assert c["shape_bucketing.window_dispatch"] == blocks * calls
    assert "shape_bucketing.group_dispatch" not in c
    rows = {k: v for k, v in c.items() if k.startswith("scheduler.rows{")}
    assert sorted(rows) == [f"scheduler.rows{{device={d}}}" for d in route["devices"]]
    assert len(rows) == 4 and sum(rows.values()) == route["rows"] * calls
    per_device = blocks // 4
    for d in route["devices"]:
        assert c[f"scheduler.dispatches{{device={d}}}"] == per_device * calls


def test_the_readers_by_arithmetic(route):
    """Blocks of equal rows, a quarter of them on each device: those of
    three devices leave the first as windows of a rung's rows and come
    back as parts of their own rows, float32 both ways."""
    got, blocks = route["readers"], route["blocks"]
    moved = blocks - blocks // 4
    there = moved * route["bucket"] * 4
    back = moved * (route["rows"] // blocks) * 4
    assert got["d2d_bytes_per_call"] == there + back
    assert got["dispatch_balance_pct"] == 100.0
    c = route["counters"]
    seconds = c["scheduler.gather_seconds"] + sum(
        v for k, v in c.items() if k.startswith("scheduler.put_seconds{"))
    assert seconds > 0
    assert got["d2d_host_ms_per_call"] == pytest.approx(1e3 * seconds / route["calls"])
    first = route["devices"][0]  # the column's own device: nothing arrives
    assert c[f"scheduler.bytes_in{{device={first}}}"] == 0


def test_no_counter_no_number(route):
    assert set(route["readers_on_no_counters"].values()) == {None}


def test_planted_faults_are_not_correct(route):
    assert route["faults"] == {"half_left_out": False, "one_answer_altered": False,
                               "state_unchanged": False}
