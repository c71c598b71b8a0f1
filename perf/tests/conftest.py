"""`test_faults.py` and `test_span_reduce.py` look a cell's verb up in a
table of the runners they were written for (and plant faults in a frame
column named `x`); a runner that came later is in neither table and
brings a test file of its own with the same checks. Its cells are skipped
in those two files, not failed with a KeyError."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OWN_TESTS = {"map_blocks_lm": "test_lm_cell.py"}  # runner -> its own file
TABLED = ("test_faults.py", "test_span_reduce.py")


def _runner_of(cell_name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}.get(cell_name)
    if cell is None:
        return None
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)["runner"]


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) not in TABLED:
            continue
        params = getattr(getattr(item, "callspec", None), "params", {})
        own = OWN_TESTS.get(_runner_of(params.get("cell")))
        if own:
            item.add_marker(pytest.mark.skip(
                reason=f"{params['cell']}: the same checks are in perf/tests/{own}"
            ))
