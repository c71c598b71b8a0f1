"""Step 2 of the trace reduction on a hand-written event list."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from perf.lib.trace import Event, reduce_events  # noqa: E402

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def hand_written():
    """Two calls in a slice of 10 s. Device 0: the program 2 x 2 s, a pad
    copy 2 x 1 s, idle 4 s; device 1: one op of 1 s."""
    ev = []
    for k, t in enumerate((0.0, 5.0)):
        ev.append(Event(HOST, "python3", "perf.issue", t, 1.0))
        ev.append(Event(HOST, "python3", "map_blocks", t + 0.1, 0.8))
        ev.append(Event(HOST, "python3", "perf.wait", t + 1.0, 4.0))
        ev.append(Event(DEV0, "XLA Modules", f"jit_concatenate({k})", t + 1.0, 1.0))
        ev.append(Event(DEV0, "XLA Modules", "jit_fn(77)", t + 2.0, 2.0))
        ev.append(Event(DEV0, "XLA Ops", "concatenate.1", t + 1.0, 1.0))
        # two ops that overlap count once in the union
        ev.append(Event(DEV0, "XLA Ops", "add_fusion", t + 2.0, 2.0))
        ev.append(Event(DEV0, "XLA Ops", "copy.2", t + 2.5, 0.5))
    ev.append(Event(DEV1, "XLA Ops", "add_fusion", 3.0, 1.0))
    ev.append(Event(DEV1, "XLA Modules", "jit_fn(77)", 3.0, 1.0))
    # outside the marks: clipped away
    ev.append(Event(DEV0, "XLA Ops", "warmup", -3.0, 2.0))
    ev.append(Event(HOST, "other-thread", "noise", 0.0, 10.0))
    return ev


def test_busy_idle_modules_and_gaps():
    r = reduce_events(hand_written(), r"^jit_fn$")
    assert r["window_s"] == pytest.approx(10.0)
    d0, d1 = r["per_device"]
    assert d0["plane"] == DEV0 and d0["busy_s"] == pytest.approx(6.0)
    assert d0["idle_pct"] == pytest.approx(40.0)
    assert d1["busy_s"] == pytest.approx(1.0) and d1["idle_pct"] == pytest.approx(90.0)
    assert r["busy_s"] == pytest.approx(3.5)  # the mean over the devices
    assert r["program_seconds"] == pytest.approx(5.0)
    assert r["other_module_seconds"] == pytest.approx(2.0)
    assert r["module_seconds"]["jit_concatenate"] == pytest.approx(2.0)
    assert r["marks"] == {"perf.issue": 2, "perf.wait": 2}
    ops = dict(r["device_ops"])
    assert ops["add_fusion"] == pytest.approx(5.0) and "warmup" not in ops
    gaps = dict(r["idle_gaps"])
    # each idle gap is shared among the innermost host events under it:
    # device 0 idles [0,1) [4,6) [9,10), device 1 [0,3) [4,10)
    assert gaps["map_blocks"] == pytest.approx(3.2)
    assert gaps["perf.issue"] == pytest.approx(0.8)
    assert gaps["perf.wait"] == pytest.approx(9.0)
    assert sum(gaps.values()) == pytest.approx(4.0 + 9.0)
    assert "noise" not in gaps


def test_nothing_to_read():
    host_only = [e for e in hand_written() if e.plane == HOST]
    assert reduce_events(host_only, r"^jit_fn$") is None
    no_marks = [e for e in hand_written() if not e.name.startswith("perf.")]
    assert reduce_events(no_marks, r"^jit_fn$") is None
