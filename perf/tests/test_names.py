"""Every name in BENCHMARK.json resolves to a file and keeps to the
allowed characters; the layout is data a later PR can add to."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for g in ("configs", "workloads") for x in BENCH[g]]
    names += [m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]]
    names += [w[k] for w in BENCH["workloads"] for k in ("config", "traffic")]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for g in ("end_to_end", "per_layer"):
        got = [m["name"] for m in BENCH[g]]
        assert len(got) == len(set(got))
        for m in BENCH[g]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(pairs) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert len(BENCH["command"]) <= 32 and 1 <= BENCH["run_seconds"] <= 51
    lines = [c[k] for c in BENCH["configs"] for k in ("source", "why")]
    lines += [w["why"] for w in BENCH["workloads"]] + [m["layer"] for m in BENCH["per_layer"]]
    assert all(1 <= len(x) <= 200 and "\n" not in x and "\t" not in x for x in lines)
    assert all(len(c["reduced"]) <= 16 for c in BENCH["configs"])
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    assert cfg["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert os.path.exists(os.path.join(ROOT, body["reference"]))
    assert os.path.exists(os.path.join(ROOT, "perf", "runners", body["runner"] + ".py"))
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_metrics(cell):
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]
    assert os.path.exists(os.path.join(ROOT, "perf", "workloads", cell["traffic"] + ".json"))
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    e2e = [m for m in BENCH["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    per = [m for m in BENCH["per_layer"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    assert per
    for m in per:
        assert m["moves"] in [e["name"] for e in e2e]
        assert os.path.exists(os.path.join(ROOT, "perf", "metrics", m["name"] + ".py"))


def test_files_under_paths_are_named_from_a_names_characters():
    for path in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [x for x in dirs if x not in ("__pycache__", ".trace", ".pytest_cache")]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(d, f)
