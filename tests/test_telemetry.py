"""Structured tracing + metrics (`utils.telemetry`).

The observability subsystem's contract tests: span nesting and parent
links, bounded ring-buffer memory, thread-safety under an 8-thread
hammer, exporter formats (Chrome trace-event JSON round-trip, Prometheus
text), the `diagnostics()` wall-time attribution on a chained lazy
map→reduce (the acceptance scenario), near-zero behavior when disabled,
and the honest `executor_stats()` fallback for executors that cannot
count jit shape specializations.
"""

import json
import threading

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import config, dsl
from tensorframes_tpu.utils import telemetry as tele
from tensorframes_tpu.utils.inspection import executor_stats
from tensorframes_tpu.utils.profiling import record, reset_stats, stats

N_THREADS = 8
ITERS = 200


def _run_threads(target, n=N_THREADS):
    """tests/test_threading.py's harness: barrier start, first worker
    exception re-raised."""
    barrier = threading.Barrier(n)
    errors = []

    def wrap(i):
        try:
            barrier.wait(timeout=30)
            target(i)
        except BaseException as e:  # noqa: BLE001 — surfaced to pytest
            errors.append(e)

    threads = [threading.Thread(target=wrap, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "worker thread hung"
    if errors:
        raise errors[0]


class TestSpans:
    def test_nesting_and_parent_links(self):
        tele.reset()
        with tele.span("outer", kind="verb") as outer_id:
            with tele.span("inner", kind="stage") as inner_id:
                pass
        ss = {s.name: s for s in tele.spans()}
        assert ss["inner"].parent_id == outer_id
        assert ss["outer"].parent_id is None
        assert inner_id != outer_id
        # the parent's window contains the child's
        assert ss["outer"].t0 <= ss["inner"].t0
        assert ss["outer"].t1 >= ss["inner"].t1

    def test_disabled_records_nothing_but_counters_stay_live(self):
        tele.reset()
        reset_stats()
        with config.override(telemetry=False):
            df = tfs.TensorFrame.from_dict({"x": np.arange(6.0)})
            z = (tfs.block(df, "x") + 1.0).named("z")
            tfs.map_blocks(z, df)
        assert tele.spans() == []
        s = stats()
        assert s["map_blocks.calls"] == 1  # legacy counters unaffected
        assert s["map_blocks.rows"] == 6

    def test_error_span_still_recorded_with_error_attr(self):
        tele.reset()
        with pytest.raises(ValueError):
            with tele.span("boom", kind="stage"):
                raise ValueError("x")
        (s,) = tele.spans()
        assert s.attrs["error"] == "ValueError"

    def test_verb_span_nests_block_dispatches_with_program(self):
        tele.reset()
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(40.0)}, num_blocks=4
        )
        z = (tfs.block(df, "x") * 2.0).named("z")
        tfs.map_blocks(z, df)
        ss = tele.spans()
        verbs = [s for s in ss if s.kind == "verb"]
        dispatches = [s for s in ss if s.kind == "dispatch"]
        assert len(verbs) == 1 and verbs[0].name == "map_blocks"
        assert len(dispatches) == 4  # one per block
        by_id = {s.span_id: s for s in ss}
        for d in dispatches:
            # verb -> map_blocks.blocks (the block loop) -> dispatch
            loop = by_id[d.parent_id]
            assert loop.name == "map_blocks.blocks"
            assert loop.parent_id == verbs[0].span_id
            assert d.attrs["program"]  # graph fingerprint label

    def test_lazy_force_and_stream_chunks_attribute_to_spans(self):
        tele.reset()
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(20.0)}, num_blocks=2
        )
        lf = df.lazy().map_blocks((tfs.block(df, "x") + 1.0).named("y"))
        lf.force()
        names = [s.name for s in tele.spans()]
        assert "lazy.force" in names
        assert "lazy.force.block" in names
        # stream chunks record too (previously bypassed profiling)
        tele.reset()
        proto = tfs.TensorFrame.from_dict({"x": np.ones(4)})
        x_input = tfs.block(proto, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        chunks = (
            tfs.TensorFrame.from_dict({"x": np.ones(4)}) for _ in range(3)
        )
        tfs.reduce_blocks_stream(s, chunks)
        names = [sp.name for sp in tele.spans()]
        assert names.count("reduce_blocks_stream.chunk") == 3


class TestRingBuffer:
    def test_bounded_memory_and_dropped_count(self):
        with config.override(telemetry_ring_entries=64):
            tele.reset()  # ring rebuilt at the overridden bound
            for i in range(500):
                with tele.span(f"s{i}"):
                    pass
            assert len(tele.spans()) == 64
            assert tele.spans_dropped() == 500 - 64
            # the freshest spans survive, the oldest fell off
            assert tele.spans()[-1].name == "s499"
        tele.reset()

    def test_compile_spans_recorded_on_fresh_executor(self):
        tele.reset()
        ex = tfs.Executor()
        df = tfs.TensorFrame.from_dict({"x": np.arange(8.0)})
        z = (tfs.block(df, "x") + 3.0).named("z")
        tfs.map_blocks(z, df, executor=ex)
        kinds = {s.kind for s in tele.spans()}
        assert "compile" in kinds
        phases = {
            s.attrs.get("phase")
            for s in tele.spans()
            if s.kind == "compile"
        }
        # both the cache-miss trace phase and the per-shape XLA phase
        assert {"trace", "xla"} <= phases


class TestConcurrency:
    def test_counters_exact_under_8_threads(self):
        tele.reset()

        def work(i):
            for _ in range(ITERS):
                tele.counter_inc("hammer.total")
                tele.counter_inc("hammer.labeled", 2.0, worker=i % 2)
                tele.histogram_observe("block_rows", float(i + 1))

        _run_threads(work)
        s = stats()
        assert s["hammer.total"] == N_THREADS * ITERS
        assert (
            s["hammer.labeled{worker=0}"] + s["hammer.labeled{worker=1}"]
            == 2.0 * N_THREADS * ITERS
        )
        _, _, hists = tele.metrics_snapshot()
        (key,) = [k for k in hists if k[0] == "block_rows"]
        _, counts, hsum, hcount = hists[key]
        assert hcount == sum(counts) == N_THREADS * ITERS

    def test_spans_from_8_threads_bounded_and_well_formed(self):
        with config.override(telemetry_ring_entries=256):
            tele.reset()

            def work(i):
                for k in range(ITERS):
                    with tele.span(f"t{i}", kind="verb"):
                        with tele.span(f"t{i}.child", kind="dispatch"):
                            pass

            _run_threads(work)
            ss = tele.spans()
            assert len(ss) <= 256  # bounded no matter the volume
            by_id = {s.span_id: s for s in ss}
            for s in ss:
                # a parent link is either absent or points to an OLDER
                # span id; when the parent survived eviction it must be
                # the same thread and its window must contain the child
                if s.parent_id is None:
                    continue
                assert s.parent_id < s.span_id
                p = by_id.get(s.parent_id)
                if p is not None:
                    assert p.thread == s.thread
                    assert p.t0 <= s.t0 and p.t1 >= s.t1
        tele.reset()

    def test_concurrent_verbs_do_not_cross_parent(self):
        tele.reset()

        def work(i):
            df = tfs.TensorFrame.from_dict(
                {"x": np.arange(24.0) * (i + 1)}, num_blocks=3
            )
            z = (tfs.block(df, "x") + float(i)).named("z")
            for _ in range(4):
                tfs.map_blocks(z, df)

        _run_threads(work, n=4)
        ss = tele.spans()
        by_id = {s.span_id: s for s in ss}
        for s in ss:
            if s.kind == "dispatch" and s.parent_id in by_id:
                assert by_id[s.parent_id].thread == s.thread


class TestExporters:
    def test_chrome_trace_schema_roundtrip(self, tmp_path):
        tele.reset()
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(30.0)}, num_blocks=3
        )
        z = (tfs.block(df, "x") * 2.0).named("z")
        tfs.map_blocks(z, df)
        path = str(tmp_path / "trace.json")
        obj = tele.export_chrome_trace(path)
        with open(path) as f:
            loaded = json.load(f)
        assert loaded == obj  # round-trip: what's returned is what's on disk
        events = loaded["traceEvents"]
        assert events, "trace must be non-empty"
        for ev in events:
            assert ev["ph"] == "X"
            for k in ("name", "cat", "ts", "dur", "pid", "tid", "args"):
                assert k in ev
        # verb -> dispatch nesting survives via args span/parent ids
        verb = [e for e in events if e["cat"] == "verb"][0]
        dispatches = [e for e in events if e["cat"] == "dispatch"]
        assert dispatches
        by_id = {e["args"]["span_id"]: e for e in events}
        for d in dispatches:
            loop = by_id[d["args"]["parent_id"]]  # the block loop's span
            assert loop["args"]["parent_id"] == verb["args"]["span_id"]
            # timestamp containment = what the trace viewer nests by
            assert verb["ts"] <= d["ts"]
            assert verb["ts"] + verb["dur"] >= d["ts"] + d["dur"]

    def test_traced_chain_on_fresh_executor_exports_compiles_and_blocks(
        self, tmp_path
    ):
        """A chained map -> reduce traced on a fresh executor: the
        exported trace holds the chain's compiles and one block-labelled
        dispatch a block, each under a verb, and the answer is exact."""
        tele.reset()
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(4000, dtype=np.float32)}, num_blocks=4
        ).to_device()
        ex = tfs.Executor()
        with config.override(telemetry=True):
            mapped = tfs.map_blocks(
                (tfs.block(df, "x") * 2.0 + 1.0).named("y"), df, executor=ex
            )
            total = tfs.reduce_blocks(
                dsl.reduce_sum(
                    tfs.block(mapped, "y", tf_name="y_input"), axes=[0]
                ).named("y"),
                mapped, executor=ex,
            )
        assert float(np.asarray(total)) == float(
            (2.0 * np.arange(4000.0) + 1.0).sum()
        )
        events = tele.export_chrome_trace(str(tmp_path / "t.json"))[
            "traceEvents"
        ]
        assert [e for e in events if e["cat"] == "compile"]
        per_block = [
            e for e in events
            if e["cat"] == "dispatch" and e["args"].get("block") is not None
        ]
        assert {e["args"]["block"] for e in per_block} == {0, 1, 2, 3}
        verbs = {e["args"]["span_id"] for e in events if e["cat"] == "verb"}
        by_id = {e["args"]["span_id"]: e for e in events}

        def under_a_verb(e):
            while e is not None and e["args"]["span_id"] not in verbs:
                e = by_id.get(e["args"].get("parent_id"))
            return e is not None

        assert all(under_a_verb(e) for e in per_block)

    def test_prometheus_text_format(self):
        tele.reset()
        reset_stats()
        tele.counter_inc("demo.count", 3)
        tele.histogram_observe("verb_seconds", 0.002, verb="map_blocks")
        tele.gauge_set("stream_queue_depth", 2)
        text = tele.export_prometheus()
        assert "# TYPE tfs_demo_count counter" in text
        assert "tfs_demo_count 3" in text
        assert "# TYPE tfs_verb_seconds histogram" in text
        assert 'tfs_verb_seconds_bucket{verb="map_blocks",le="+Inf"} 1' in text
        assert 'tfs_verb_seconds_count{verb="map_blocks"} 1' in text
        assert "# TYPE tfs_stream_queue_depth gauge" in text
        # built-in process gauges ride along
        assert "tfs_executor_cache_entries" in text

    def test_histogram_bucket_monotone_cumulative(self):
        tele.reset()
        for v in (0.5, 3.0, 100.0, 1e9):
            tele.histogram_observe("block_rows", v)
        text = tele.export_prometheus()
        cum = [
            int(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith("tfs_block_rows_bucket")
        ]
        assert cum == sorted(cum)
        assert cum[-1] == 4  # +Inf bucket sees everything


class TestDiagnostics:
    def test_lazy_chain_attributes_wall_time(self):
        """The acceptance scenario: a chained lazy map→reduce over a
        multi-block frame. diagnostics() must attribute >=95% of the
        span window to named root spans and carry a per-program table
        distinguishing compile from execute time."""
        tele.reset()
        ex = tfs.Executor()  # fresh: the traced run includes compiles
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(60.0, dtype=np.float32)}, num_blocks=4
        )
        with tfs.lazy():
            m1 = tfs.map_blocks(
                (tfs.block(df, "x") * 2.0).named("y"), df, executor=ex
            )
            m2 = tfs.map_blocks(
                (tfs.block(m1, "y") + 1.0).named("z"), m1, executor=ex
            )
            z_in = tfs.block(m2, "z", tf_name="z_input")
            total = tfs.reduce_blocks(
                dsl.reduce_sum(z_in, axes=[0]).named("z"), m2, executor=ex
            )
        assert abs(float(np.asarray(total)) - float(
            (np.arange(60.0) * 2 + 1).sum()
        )) < 1e-3
        agg = tele.span_aggregates()
        assert agg["coverage"] >= 0.95, agg
        assert agg["by_program"], "program attribution table is empty"
        some = next(iter(agg["by_program"].values()))
        assert {"compile_s", "execute_s", "host_sync_s"} <= set(some)
        # at least one program saw both a compile and a dispatch
        assert any(
            p["compiles"] > 0 and p["dispatches"] > 0
            for p in agg["by_program"].values()
        )
        report = tfs.diagnostics(ex)
        assert "attributed" in report
        assert "programs (by graph fingerprint):" in report
        assert "recompile storm" in report

    def test_diagnostics_never_raises_when_empty(self):
        tele.reset()
        out = tfs.diagnostics()
        assert "tensorframes-tpu diagnostics" in out

    def test_host_sync_span_recorded_at_materialization(self):
        tele.reset()
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(10.0, dtype=np.float32)}
        ).to_device()
        z = (tfs.block(df, "x") + 1.0).named("z")
        out = tfs.map_blocks(z, df)
        out.column("z").host_values()
        kinds = [s.kind for s in tele.spans()]
        assert "host_sync" in kinds
        assert "transfer" in kinds  # the to_device H2D leaf
        _, _, hists = tele.metrics_snapshot()
        assert any(k[0] == "d2h_bytes" for k in hists)
        assert any(k[0] == "h2d_bytes" for k in hists)


class TestReset:
    def test_reset_clears_everything_but_registered_gauges(self):
        tele.reset()
        tele.counter_inc("x")
        tele.gauge_set("y", 1.0)
        tele.histogram_observe("block_rows", 5.0)
        with tele.span("s"):
            pass
        tele.reset()
        assert tele.spans() == []
        counters, gauges, hists = tele.metrics_snapshot()
        assert counters == {}
        assert hists == {}
        # built-in registered gauges survive (they read live state)
        assert ("executor_cache_entries", ()) in gauges


class TestExecutorStatsHonesty:
    def test_stub_without_shape_compiles_gets_estimated_flag(self):
        class Stub:
            compile_count = 7
            cache_hits = 1
            cache_misses = 2
            _cache = {}

        s = executor_stats(Stub())
        # compile_count must NOT leak into jit_shape_compiles anymore
        assert s["jit_shape_compiles"] == 0
        assert s["jit_shape_compiles_estimated"] is True
        assert s["compile_count"] == 7

    def test_real_executor_has_no_flag(self):
        ex = tfs.Executor()
        df = tfs.TensorFrame.from_dict({"x": np.arange(4.0)})
        z = (tfs.block(df, "x") + 1.0).named("z")
        tfs.map_blocks(z, df, executor=ex)
        s = executor_stats(ex)
        assert "jit_shape_compiles_estimated" not in s
        assert s["jit_shape_compiles"] >= 1

    def test_native_executor_parity(self):
        """NativeExecutor implements jit_shape_compiles (== its
        compile_count), so it reports the exact key set with no
        estimated flag — parity with the in-process executor."""
        from tensorframes_tpu.runtime.native_executor import NativeExecutor

        ex = NativeExecutor.for_host(object())  # host never touched here
        s = executor_stats(ex)
        assert "jit_shape_compiles_estimated" not in s
        assert s["jit_shape_compiles"] == s["compile_count"] == 0
        assert set(s) == {
            "compile_count", "cache_hits", "cache_misses", "cache_entries",
            "jit_shape_compiles", "faults", "admission",
        }

    def test_program_shape_compiles_per_program(self):
        ex = tfs.Executor()
        df = tfs.TensorFrame.from_dict({"x": np.arange(30.0)})
        z = (tfs.block(df, "x") + 1.0).named("z")
        # scheduler off: per-device placement would add one jit
        # specialization per (device, shape) pair and the point here is
        # the per-SHAPE count of a single-device program
        with config.override(shape_bucketing=False, block_scheduler="off"):
            for nb in (1, 2, 3):
                tfs.map_blocks(z, df.repartition(nb), executor=ex)
        per = ex.program_shape_compiles()
        assert sum(per.values()) == ex.jit_shape_compiles()
        # 3 repartitions -> 3 distinct block shapes of ONE program
        (key,) = [k for k in per if k[0] == "block"]
        assert per[key] == 3


class TestPrometheusExposition:
    """Exposition-format correctness (ISSUE 8 satellite): escaped label
    values and # HELP headers."""

    def test_label_value_escaping_round_trip(self):
        evil = 'a\\b"c\nd'  # backslash, quote, newline — a shard path
        tele.counter_inc("ingest_chunks", 3.0, tfs_shard_path=evil)
        text = tele.export_prometheus()
        line = next(
            l for l in text.splitlines()
            if l.startswith("tfs_ingest_chunks{")
        )
        # one physical line (the raw newline would split the sample)
        assert "\n" not in line
        assert line.endswith(" 3")
        # parse the label value back per the exposition grammar
        m = __import__("re").match(
            r'^tfs_ingest_chunks\{tfs_shard_path="((?:[^"\\]|\\.)*)"\} 3$',
            line,
        )
        assert m, line
        unescaped = (
            m.group(1)
            .replace("\\\\", "\x00")
            .replace('\\"', '"')
            .replace("\\n", "\n")
            .replace("\x00", "\\")
        )
        assert unescaped == evil

    def test_help_lines_accompany_types(self):
        tele.counter_inc("host_sync", 1.0)
        tele.histogram_observe("verb_seconds", 0.1, verb="map_blocks")
        text = tele.export_prometheus()
        lines = text.splitlines()
        for i, l in enumerate(lines):
            if l.startswith("# TYPE "):
                name = l.split()[2]
                assert lines[i - 1].startswith(f"# HELP {name} "), (
                    f"# TYPE {name} without a preceding # HELP"
                )
        assert any(
            l.startswith("# HELP tfs_host_sync ") for l in lines
        )


class TestDiagnosticsFormats:
    """diagnostics(format=) (ISSUE 8 satellite): structured JSON beside
    the byte-identical default text rendering."""

    def test_json_is_a_serializable_dict(self):
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(64, dtype=np.float32)}, num_blocks=2
        )
        tfs.map_blocks((tfs.block(df, "x") * 2.0).named("y"), df)
        d = tfs.diagnostics(format="json")
        assert isinstance(d, dict)
        json.dumps(d)  # fully serializable, no default= crutch
        for section in (
            "telemetry_enabled", "window", "verbs", "phases", "programs",
            "cost", "memory", "health", "faults", "forensics",
            "executor", "gauges",
        ):
            assert section in d, f"missing section {section!r}"
        assert d["verbs"]["map_blocks"]["calls"] == 1

    def test_text_rendering_matches_data(self):
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(64, dtype=np.float32)}, num_blocks=2
        )
        tfs.map_blocks((tfs.block(df, "x") * 2.0).named("y"), df)
        default = tfs.diagnostics()
        explicit = tfs.diagnostics(format="text")
        assert isinstance(default, str)
        # same renderer, same sections (wall-clock fields in the window
        # line differ between calls; compare structure not timings)
        assert default.splitlines()[0] == explicit.splitlines()[0]
        assert "verbs:" in default and "executor:" in default

    def test_unknown_format_raises(self):
        with pytest.raises(ValueError):
            tfs.diagnostics(format="yaml")


class TestCrossThreadSpanAttribution:
    """Ingest PipeStage worker threads + the scheduler dispatch path
    (ISSUE 8 satellite): stage spans recorded off-thread must parent to
    the consuming verb (explicit parent id + stage label) — the
    exported Chrome trace contains NO orphan parent ids."""

    def test_pipelined_stream_dataset_trace_has_no_orphans(self, tmp_path):
        pytest.importorskip("pyarrow")
        from tensorframes_tpu import io as tio
        from tensorframes_tpu.frame import TensorFrame
        from tensorframes_tpu.io import stream_dataset

        rng = np.random.RandomState(0)
        parts = []
        for i, n in enumerate((300, 200, 250)):
            x = rng.rand(n).astype(np.float32)
            parts.append(x)
            tio.write_parquet(
                TensorFrame.from_dict({"x": x}, num_blocks=2),
                str(tmp_path / f"shard-{i:03d}.parquet"),
            )
        expected = float(np.concatenate(parts).sum())

        df0 = TensorFrame.from_dict({"x": np.arange(2.0, dtype=np.float32)})
        g = dsl.reduce_sum(
            tfs.block(df0, "x", tf_name="x_input"), axes=[0]
        ).named("x")
        with config.override(ingest_pipeline=True):
            total = tfs.reduce_blocks_stream(
                g, stream_dataset(str(tmp_path), decode_workers=2)
            )
        assert abs(float(np.asarray(total)) - expected) < 1e-2

        trace = tele.export_chrome_trace()
        events = trace["traceEvents"]
        ids = {e["args"]["span_id"] for e in events}
        orphans = [
            e for e in events
            if e["args"].get("parent_id") is not None
            and e["args"]["parent_id"] not in ids
        ]
        assert not orphans, [
            (e["name"], e["args"]) for e in orphans
        ]
        # stage spans exist, labeled, and are parented (decode runs on
        # pool workers, transfer on its own thread — neither inherits
        # contextvars, both must carry the explicit parent)
        stages = [e for e in events if e["cat"] == "stage"]
        by_stage = {e["args"].get("stage") for e in stages}
        assert "decode" in by_stage, by_stage
        assert "transfer-stage" in by_stage, by_stage
        off_thread = [
            e for e in stages
            if e["args"].get("stage") in ("decode", "transfer-stage")
        ]
        assert off_thread
        for e in off_thread:
            assert e["args"].get("parent_id") in ids, e["args"]

    def test_serial_pipeline_stages_nest_naturally(self, tmp_path):
        pytest.importorskip("pyarrow")
        from tensorframes_tpu import io as tio
        from tensorframes_tpu.frame import TensorFrame
        from tensorframes_tpu.io import stream_dataset

        x = np.arange(100, dtype=np.float32)
        tio.write_parquet(
            TensorFrame.from_dict({"x": x}, num_blocks=2),
            str(tmp_path / "shard-000.parquet"),
        )
        df0 = TensorFrame.from_dict({"x": np.arange(2.0, dtype=np.float32)})
        g = dsl.reduce_sum(
            tfs.block(df0, "x", tf_name="x_input"), axes=[0]
        ).named("x")
        with config.override(ingest_pipeline=False):
            total = tfs.reduce_blocks_stream(
                g, stream_dataset(str(tmp_path), decode_workers=2)
            )
        assert abs(float(np.asarray(total)) - float(x.sum())) < 1e-3
        events = tele.export_chrome_trace()["traceEvents"]
        ids = {e["args"]["span_id"] for e in events}
        stages = [
            e for e in events
            if e["cat"] == "stage" and e["args"].get("stage")
        ]
        assert any(e["args"].get("stage") == "decode" for e in stages)
        # every stage-labeled span parents to the pipeline root (which
        # is itself in the trace — no orphan parent ids)
        for e in stages:
            assert e["args"].get("parent_id") in ids


# ---------------------------------------------------------------------------
# spans below the verb (ISSUE 26): plan / cut / pad / dispatch / unpad /
# concat, self time by name
# ---------------------------------------------------------------------------

# children of `<verb>.plan`; map_rows' dense route classifies nothing
_PLAN = ["graph.analyze", "frame.match", "executor.lookup", "scheduler.plan"]

# (verb, rows, blocks) -> the spans of ONE warm call: {name: (count, parent)}.
# A frame of several blocks is device-resident, so its blocks off their
# rung are windows of the column (`shape_policy.block_feeds`): the window
# sits in `shape.pad`, and there is no separate cut. The one-block frames
# are numpy-backed and shorter than their rung: the replicated pad, as it
# was, and no cut in either verb (a block that is the whole frame is fed
# the column as it is: both verbs run `api._run_blocks`). The tests run on
# several virtual devices, so four blocks spread over four of them come
# back to the anchor in ONE `frame.gather` (ISSUE 38), inside the concat:
# `map_rows`' by the scheduler's own choice, `map_blocks`' where the caller
# names the devices (`devices=`: "-spread"). Left to the scheduler, a
# row-local `map_blocks` stays on the device that holds its column (the
# home plan, ISSUE 39), where its four equal blocks are one group.
_CALLS = {
    "map_blocks-1block-on-rung": ("map_blocks", 64, 1, {}),
    "map_blocks-1block-off-rung": ("map_blocks", 40, 1, {
        "shape.pad": (1, "map_blocks.blocks"),
        "shape.unpad": (1, "map_blocks.blocks"),
    }),
    "map_blocks-4blocks-off-rung": ("map_blocks", 40, 4, {
        "map_blocks.block": (1, "map_blocks.blocks"),
    }),
    "map_blocks-4blocks-off-rung-spread": ("map_blocks", 40, 4, {
        "shape.pad": (4, "map_blocks.blocks"),
        "shape.unpad": (4, "map_blocks.blocks"),
        "frame.concat": (1, "map_blocks"),
        "frame.gather": (1, "frame.concat"),
    }),
    "map_rows-dense": ("map_rows", 40, 1, {
        "shape.pad": (1, "map_rows.blocks"),
        "shape.unpad": (1, "map_rows.blocks"),
    }),
    "map_rows-4blocks-off-rung": ("map_rows", 40, 4, {
        "shape.pad": (4, "map_rows.blocks"),
        "shape.unpad": (4, "map_rows.blocks"),
        "frame.concat": (1, "map_rows"),
        "frame.gather": (1, "frame.concat"),
    }),
}


def _warm_call(case):
    """The case's verb call, twice: the first compiles, the ring is
    cleared, and the second is the one the test reads."""
    import functools

    import jax

    verb, rows, blocks, extra = _CALLS[case]
    x = np.arange(rows, dtype=np.float32)
    if blocks > 1:
        x = jax.device_put(x)
    df = tfs.TensorFrame(
        [tfs.Column("x", x)],
        [int(v) for v in np.linspace(0, rows, blocks + 1)],
    )
    ph = (tfs.block if verb == "map_blocks" else tfs.row)(df, "x")
    fetch = (ph * 2.0).named("z")
    run = getattr(tfs, verb)
    if case.endswith("-spread"):
        run = functools.partial(run, devices=jax.local_devices()[:4])
    run(fetch, df)
    tele.reset()
    out = run(fetch, df)
    np.testing.assert_array_equal(
        np.asarray(out["z"].values), 2.0 * np.arange(rows, dtype=np.float32)
    )
    return verb, blocks, extra


class TestSpansBelowTheVerb:
    @pytest.mark.parametrize("case", sorted(_CALLS))
    def test_span_tree_of_one_call(self, case):
        verb, blocks, extra = _warm_call(case)
        ss = tele.spans()
        by_id = {s.span_id: s for s in ss}
        want = {
            verb: (1, None),
            f"{verb}.plan": (1, verb),
            f"{verb}.blocks": (1, verb),
            f"{verb}.block": (blocks, f"{verb}.blocks"),
            **{n: (1, f"{verb}.plan") for n in _PLAN},
            **extra,
        }
        if verb == "map_blocks":
            want["shape.classify"] = (1, "map_blocks.plan")
        names = [s.name for s in ss]
        assert set(names) == set(want)  # exactly these, no others
        (root,) = [s for s in ss if s.kind == "verb"]
        for s in ss:
            count, parent = want[s.name]
            assert names.count(s.name) == count, s.name
            if parent is None:
                assert s.parent_id is None
                continue
            p = by_id[s.parent_id]
            assert p.name == parent, (s.name, p.name)
            assert p.t0 <= s.t0 and s.t1 <= p.t1  # inside its parent
            # every span of a call reaches the call's verb span: the
            # identifier the spans of one call share
            node = s
            while node.parent_id is not None:
                node = by_id[node.parent_id]
            assert node is root
        kinds = {s.name: s.kind for s in ss}
        assert kinds[f"{verb}.plan"] == kinds[f"{verb}.blocks"] == "stage"
        assert kinds[f"{verb}.block"] == "dispatch"
        for s in ss:
            if s.name in ("shape.pad", "shape.unpad"):
                assert s.attrs["bucket"] > s.attrs["rows"]

    @pytest.mark.parametrize("case", sorted(_CALLS))
    def test_disabled_leaves_the_ring_empty(self, case):
        with config.override(telemetry=False):
            _warm_call(case)
            assert tele.spans() == []

    @pytest.mark.parametrize("what", ["overlapping-children", "orphan"])
    def test_by_name_self_time(self, what):
        ms = 1e-3

        def sp(i, parent, name, t0, t1, kind="span"):
            return tele.Span(i, parent, name, kind, t0 * ms, t1 * ms, 0)

        if what == "overlapping-children":
            # a 10 ms parent; children [1, 5] and [3, 8] overlap and
            # cover 7 ms of it: 3 ms are its own
            ss = [sp(2, 1, "child", 1, 5), sp(3, 1, "child", 3, 8),
                  sp(1, None, "parent", 0, 10, "verb")]
            agg = tele.span_aggregates(ss)
            assert agg["roots"] == 1
            parent, child = agg["by_name"]["parent"], agg["by_name"]["child"]
            assert parent["count"] == 1 and child["count"] == 2
            assert parent["seconds"] == pytest.approx(10 * ms)
            assert parent["self_seconds"] == pytest.approx(3 * ms)
            assert child["seconds"] == pytest.approx(9 * ms)
            assert child["self_seconds"] == pytest.approx(9 * ms)
        else:
            # span 7's parent (id 5) has left the ring: 7 is a root, and
            # its own child still comes off its self time
            ss = [sp(8, 7, "leaf", 2, 3), sp(7, 5, "orphan", 0, 4),
                  sp(9, None, "root", 5, 6)]
            agg = tele.span_aggregates(ss)
            assert agg["roots"] == 2
            assert agg["covered"] == pytest.approx(5 * ms)
            assert agg["by_name"]["orphan"]["self_seconds"] == pytest.approx(3 * ms)
            assert agg["by_name"]["leaf"]["self_seconds"] == pytest.approx(1 * ms)

    def test_diagnostics_prints_the_by_name_table(self):
        _warm_call("map_blocks-4blocks-off-rung-spread")
        data = tfs.diagnostics(format="json")
        assert data["phases"]["shape.pad"]["count"] == 4
        assert "verb_roofline" not in data["cost"]
        text = tfs.diagnostics()
        (line,) = [l for l in text.splitlines()
                   if l.strip().startswith("map_blocks.blocks")]
        assert "n=1" in line and "total=" in line and "self=" in line


# ---------------------------------------------------------------------------
# promotion (ISSUE 29): what a bought exact shape shows
# ---------------------------------------------------------------------------


def _promoted(monkeypatch, verb, ex):
    """A resident one-block frame of 40 rows on a rung (64) that has
    seen another size first (ISSUE 32: a rung's first size runs exact
    and pays nothing). Its first call pays a rent far over any
    compile's price (a bandwidth of one byte a second) and so buys the
    exact shape: ``(call, compiled)``, the next call of the verb and
    the executable the promotion compiled."""
    import jax

    from tensorframes_tpu import shape_policy as sp
    from tensorframes_tpu.runtime import costmodel

    monkeypatch.setitem(costmodel.DEVICE_PEAKS, "cpu", {"hbm_bytes_s": 1.0})
    bought, compile_exact = [], sp._compile_exact

    def spy(*args):
        bought.append(compile_exact(*args))
        return bought[-1]

    monkeypatch.setattr(sp, "_compile_exact", spy)
    x = np.arange(40, dtype=np.float32)
    df = tfs.TensorFrame([tfs.Column("x", jax.device_put(x))], [0, 40])
    ph = (tfs.block if verb == "map_blocks" else tfs.row)(df, "x")
    fetch = (ph * 2.0).named("z")
    first = tfs.TensorFrame([tfs.Column("x", jax.device_put(x[:39]))], [0, 39])
    getattr(tfs, verb)(fetch, first, executor=ex)
    tele.reset()

    def call():
        out = getattr(tfs, verb)(fetch, df, executor=ex)
        np.testing.assert_array_equal(np.asarray(out["z"].values), 2.0 * x)

    call()
    assert sp.drain(ex, timeout=60)
    (compiled,) = bought
    return call, compiled


@pytest.mark.parametrize("verb", ["map_blocks", "map_rows"])
def test_span_tree_of_a_promoted_call(verb, monkeypatch):
    call, _ = _promoted(monkeypatch, verb, tfs.Executor())
    # the compile ran on a thread of its own, outside any verb span
    (promote,) = [s for s in tele.spans() if s.name == "shape.promote"]
    assert promote.kind == "compile" and promote.parent_id is None
    (verb_span,) = [s for s in tele.spans() if s.kind == "verb"]
    assert promote.thread != verb_span.thread
    assert promote.attrs["rows"] == 40 and promote.attrs["program"]
    assert promote.attrs["rent"] >= promote.attrs["price"] > 0
    tele.reset()
    call()
    ss = tele.spans()
    names = {s.name for s in ss}
    assert not names & {"shape.pad", "shape.unpad", "frame.cut", "shape.promote"}
    (block,) = [s for s in ss if s.name == f"{verb}.block"]
    assert block.attrs["bucket"] == block.attrs["rows"] == 40
    c = tele.flat_counters()
    assert c["shape_bucketing.promoted_dispatch"] == 1
    assert c["shape_bucketing.pad_rows"] == 0
    assert "shape_bucketing.padded_dispatch" not in c


def test_diagnostics_bucketing_line_carries_the_promotion_counters(monkeypatch):
    call, _ = _promoted(monkeypatch, "map_blocks", tfs.Executor())
    call()
    tele.counter_inc("shape_bucketing.promotion_failed", 2)
    tele.counter_inc("shape_bucketing.promotion_unpriced", 3)
    tele.counter_inc("shape_bucketing.first_size_dispatch", 4)
    bk = tfs.diagnostics(format="json")["bucketing"]
    # the padded call was its rung's second size (`_promoted`)
    assert (bk["first_size_dispatches"], bk["rungs_widened"]) == (4, 1)
    assert (bk["padded_dispatches"], bk["promoted_dispatches"]) == (1, 1)
    assert (bk["promotions"], bk["promotions_failed"]) == (1, 2)
    assert bk["promotions_unpriced"] == 3
    (line,) = [l for l in tfs.diagnostics().splitlines()
               if l.startswith("bucketing:")]
    assert "1 padded dispatch(es)" in line
    assert "1 promoted dispatch(es) on 1 exact shape(s) bought" in line
    assert "(2 failed, 3 unpriced)" in line
    assert "4 first-size dispatch(es) at their exact shape" in line
    assert "1 rung(s) widened by a second size" in line


@pytest.mark.parametrize("verb", ["map_blocks", "map_rows"])
def test_promoted_executable_keeps_the_module_name(verb, monkeypatch):
    """A promoted shape is the same `jax.jit` compiled ahead of time:
    in a device trace it is still `jit_fn`, so `program_roofline` and
    `copy_device_pct` read it as the verb's program."""
    import re

    _, compiled = _promoted(monkeypatch, verb, tfs.Executor())
    assert re.search(r"HloModule (\w+)", compiled.as_text()).group(1) == "jit_fn"


@pytest.mark.parametrize("program", ["callable_for", "vmap-rows"])
def test_verb_programs_lower_to_a_module_named_jit_fn(program):
    """The benchmark finds the verb's own program in the device trace by
    its XLA module name (`program_modules` = `^jit_fn$` in both files
    under perf/configs/). A rename of `ops.lowering.build_callable`'s
    inner function goes together with a `benchmark` PR that changes
    that pattern."""
    import re

    df = tfs.TensorFrame.from_dict({"x": np.arange(8, dtype=np.float32)})
    ex = tfs.Executor()
    if program == "callable_for":
        graph, fetches = dsl.build((tfs.block(df, "x") + 3.0).named("z"))
        fn = ex.callable_for(graph, fetches, ["x"])
    else:
        fetch = (tfs.row(df, "x") + 3.0).named("z")
        tfs.map_rows(fetch, df, executor=ex)
        graph, fetches = dsl.build(fetch)

        def never():
            raise AssertionError("map_rows did not cache its program here")

        fn = ex.cached("vmap-rows", graph, fetches, ["x"], never)
    text = fn.__wrapped__.lower(np.arange(8, dtype=np.float32)).as_text()
    assert re.search(r"module @(\S+)", text).group(1) == "jit_fn"


@pytest.mark.parametrize("helper", ["block_window", "block_unpad"])
def test_window_helpers_lower_to_modules_of_their_own(helper):
    """The window and the unpad of `shape_policy` are copies around the
    verb's program: in a device trace they must not count as `jit_fn`
    (the benchmark's `program_roofline`) but under their own names
    (`copy_device_pct`)."""
    import re

    from tensorframes_tpu import shape_policy as sp

    x = np.arange(32, dtype=np.float32)
    args = (16, np.int32(3), x) if helper == "block_window" else (3, 10, x)
    text = getattr(sp, helper).lower(*args).as_text()
    name = re.search(r"module @(\S+)", text).group(1)
    assert name == f"jit_{helper}" and not re.match(r"^jit_fn$", name)
