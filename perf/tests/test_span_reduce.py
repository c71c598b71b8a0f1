"""`perf/lib/spans.py` on a hand-written list of spans and calls, and the
five readers over each cell's CPU rehearsal: a number each, and the four
`*_host_ms_per_call` add up to the verb span within the unattributed
share."""

import collections
import importlib
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.lib import harness, spans  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
READERS = ["plan_host_ms_per_call", "pad_host_ms_per_call",
           "dispatch_host_ms_per_call", "cut_concat_host_ms_per_call",
           "verb_unattributed_pct"]

from tensorframes_tpu.utils.telemetry import Span  # noqa: E402

Call = collections.namedtuple("Call", "issued returned")
MS = 1e-3


def one_call(first_id, at, blocks):
    """The spans of one map_blocks call issued at `at` ms, in the order
    the ring gets them (a span is recorded when it ends): 2 ms of plan
    with 1.5 ms of children, then `blocks` blocks of cut 1, pad 2,
    dispatch 3, unpad 1 and 1 ms of glue, a 2 ms concat, and 1 ms of the
    verb's own before, between and after."""
    ids = iter(range(first_id, first_id + 1000))
    verb, plan, loop = next(ids), next(ids), next(ids)
    t = at + 0.5
    out = [
        Span(next(ids), plan, "graph.analyze", "span", t * MS, (t + 1.0) * MS, 0, {}),
        Span(next(ids), plan, "frame.match", "span", (t + 1.0) * MS, (t + 1.5) * MS, 0, {}),
        Span(plan, verb, "map_blocks.plan", "stage", t * MS, (t + 2.0) * MS, 0, {}),
    ]
    t += 2.25
    t_loop = t
    for b in range(blocks):
        for name, kind, dur in (("frame.cut", "span", 1.0), ("shape.pad", "span", 2.0),
                                ("map_blocks.block", "dispatch", 3.0),
                                ("shape.unpad", "span", 1.0)):
            out.append(Span(next(ids), loop, name, kind, t * MS, (t + dur) * MS, 0,
                            {"block": b}))
            t += dur
        t += 1.0  # the loop's own glue
    out.append(Span(loop, verb, "map_blocks.blocks", "stage", t_loop * MS, t * MS, 0, {}))
    out.append(Span(next(ids), verb, "frame.concat", "span", t * MS, (t + 2.0) * MS, 0, {}))
    t += 2.25
    out.append(Span(verb, None, "map_blocks", "verb", at * MS, t * MS, 0, {"rows": 8}))
    return out, Call(at * MS - 0.1 * MS, t * MS + 0.1 * MS)


def test_two_whole_calls_are_read_and_the_evicted_one_is_not():
    first, call1 = one_call(1, 100.0, blocks=2)
    second, call2 = one_call(1001, 200.0, blocks=2)
    third, call3 = one_call(2001, 300.0, blocks=4)
    # the ring lost the head of the first call (its plan, its first block)
    ring = first[6:] + second + third
    got = spans.per_call(ring, [call1, call2, call3])
    assert got["calls"] == 2
    # call 2: 2 blocks, call 3: 4 blocks; per-call means by hand
    assert got["plan_ms"] == pytest.approx(2.0)
    assert got["pad_ms"] == pytest.approx((2 * 3.0 + 4 * 3.0) / 2)
    assert got["cut_concat_ms"] == pytest.approx((2 * 1.0 + 4 * 1.0) / 2 + 2.0)
    assert got["dispatch_ms"] == pytest.approx((2 * 3.0 + 4 * 3.0) / 2 + (2 + 4) / 2)
    verb = (1.0 + 2.0 + 2.0 + (2 * 8.0 + 4 * 8.0) / 2)
    assert got["verb_ms"] == pytest.approx(verb)
    assert got["clock_ms"] == pytest.approx(verb + 0.2)
    assert got["unattributed_pct"] == pytest.approx(100.0 * 1.0 / verb)
    assert got["by_name"]["map_blocks.plan"]["self_ms"] == pytest.approx(0.5)
    assert got["by_name"]["frame.cut"]["per_call"] == pytest.approx(3.0)
    parts = (got["plan_ms"] + got["pad_ms"] + got["dispatch_ms"] + got["cut_concat_ms"])
    assert parts + got["unattributed_pct"] / 100.0 * verb == pytest.approx(verb)


def test_nothing_whole_nothing_read():
    first, call1 = one_call(1, 100.0, blocks=2)
    assert spans.per_call(first[6:], [call1]) is None
    assert spans.per_call([], [call1]) is None
    assert spans.per_call(first, []) is None


def test_a_program_without_the_table_gives_none(monkeypatch):
    """The parent commit's `span_aggregates` has no `by_name`: every
    reader then leaves its metric out and raises nothing."""
    from tensorframes_tpu.utils import telemetry

    real = telemetry.span_aggregates

    def older(span_list=None):
        got = real(span_list)
        got.pop("by_name")
        return got

    monkeypatch.setattr(telemetry, "span_aggregates", older)
    first, call1 = one_call(1, 100.0, blocks=2)
    assert spans.per_call(first, [call1]) is None
    ctx = types.SimpleNamespace(traced_calls=[call1])
    for name in READERS:
        assert importlib.import_module("perf.metrics." + name).read(ctx) is None


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_readers_over_the_rehearsal(cell, tmp_path, capsys):
    _, entry, config, traffic = harness.load_cell(ROOT, cell)
    for m in BENCH["per_layer"]:
        if m["name"] in READERS:
            assert cell in m["workloads"] and m["source"] == "program_span"
    traffic = {**traffic, **traffic["rehearse"]}
    env = harness.make_env(ROOT, entry, config, traffic, 2147483659, rehearse=True)
    runner = harness.make_runner(env)
    got = harness.measure(env, runner, 0.3, str(tmp_path / "trace"), 0.3)
    ctx = types.SimpleNamespace(
        traced_calls=[c for c in got.traced_calls if c.error is None])
    values = {
        name: importlib.import_module("perf.metrics." + name).read(ctx)
        for name in READERS
    }
    assert all(isinstance(v, float) for v in values.values()), values
    read = ctx.spans_per_call
    assert 1 <= read["calls"] <= len(ctx.traced_calls)
    parts = sum(v for k, v in values.items() if k.endswith("_host_ms_per_call"))
    own = values["verb_unattributed_pct"] / 100.0 * read["verb_ms"]
    assert parts + own == pytest.approx(read["verb_ms"], rel=1e-6)
    blocks = int(traffic["blocks"])
    assert read["by_name"][runner_verb(config) + ".block"]["per_call"] == blocks
    # the by-name table goes out once, on a line of its own
    printed = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith('{"spans_per_call"')]
    assert len(printed) == 1 and json.loads(printed[0])["spans_per_call"]["calls"] == read["calls"]


def runner_verb(config):
    return {"map_chain": "map_blocks", "map_rows_mlp": "map_rows"}[config["runner"]]
