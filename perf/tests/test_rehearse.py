"""The CPU rehearsal of every cell at the traffic file's tiny sizes, and
the refusal to measure without a TPU. Each run is a process of its own,
as the driver starts them."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def run(cell, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "perf/run.py", "--workload", cell, "--seed", "2147483659",
         "--seconds", "0.5", *more],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_is_correct_and_prints_no_metric(cell, trace):
    p = run(cell, "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert list(line)[-1] == "compared"
    assert "correct: True" in p.stderr.strip().splitlines()[-1]


def test_no_tpu_no_line():
    p = run(CELLS[0], "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
