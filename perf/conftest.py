"""`perf/tests/conftest.py` skips a later runner's cells in
`test_faults.py` and `test_span_reduce.py` by a table (`OWN_TESTS`) that
cannot be added to without an edit; this conftest, a directory above and
loaded for `perf/tests` too, does the same for the runners that came
after it. Their cells bring the same checks in a test file of their own."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OWN_TESTS = {"map_blocks_lm_latent": "test_lm_latent_cell.py"}  # runner -> its own file
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
