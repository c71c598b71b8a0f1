"""Plan-level explain_analyze, persistent workload profiles, and
cost-model accuracy tracking (ISSUE 11).

Covers `tfs.explain_analyze` (execute a lazy plan, attribute >= 95% of
its wall time to stage spans, join every cached fingerprint with the
cost ledger's modeled flops/bytes), the `runtime.profiler`
`WorkloadProfile` (snapshot -> save -> load -> merge -> diff: exact
round trips, zero structural drift across re-runs of one workload,
loud refusal to merge incomparable histogram ladders, cross-process
load), cost-model residuals (`runtime.costmodel.residuals` + the
`costmodel_residual` gauge family + diagnostics flagging), bucket-fill
accounting (`bucket_fill{verb=}` at every bucketed dispatch + the
diagnostics pad-waste line), the `config.histogram_buckets` override
(defaults byte-identical), the single-clock `utils.profiling.record`
contract, the `/profile` route, and `tools/profile_report.py`.
"""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import config
from tensorframes_tpu import dsl
from tensorframes_tpu.runtime import costmodel
from tensorframes_tpu.runtime import profiler
from tensorframes_tpu.runtime.executor import Executor
from tensorframes_tpu.utils import telemetry

import jax


_UNIQ = iter(range(10_000))


def _frame(rows=4100, blocks=8):
    return tfs.TensorFrame.from_dict(
        {"x": np.arange(rows, dtype=np.float32)}, num_blocks=blocks
    ).to_device()


def _lazy_chain(df, ex, scale=None):
    """A chained lazy map -> (pending) with a per-call unique constant
    so every test compiles a FRESH fingerprint (the ledger captures
    modeled cost only at compile events; a cache hit would leave the
    cost fields honestly None)."""
    scale = float(next(_UNIQ) + 2) if scale is None else scale
    return df.lazy().map_blocks(
        (tfs.block(df, "x") * scale + 1.0).named("y"), executor=ex
    )


def _run_reduce(lf, ex):
    return lf.reduce_blocks(
        dsl.reduce_sum(
            tfs.block(lf, "y", tf_name="y_input"), axes=[0]
        ).named("y"),
        executor=ex,
    )


# ---------------------------------------------------------------------------
# explain_analyze
# ---------------------------------------------------------------------------


class TestExplainAnalyze:
    def test_chained_lazy_acceptance(self):
        """Acceptance: explain_analyze on a chained lazy map→reduce
        attributes >= 95% of plan wall time to stages and reports
        modeled-vs-achieved cost for every cached fingerprint."""
        ex = Executor()
        df = _frame()
        lf = _lazy_chain(df, ex)
        rep = tfs.explain_analyze(lambda: _run_reduce(lf, ex), format="json")

        assert rep["coverage"] >= 0.95, rep
        assert rep["wall_s"] > 0 and rep["spans"] > 0
        cached = {str(k[1]) for k in ex.cache_keys()}
        assert cached, "chain cached no programs"
        progs = {p["program"]: p for p in rep["programs"]}
        for fp in cached:
            assert fp in progs, f"cached program {fp} missing"
            p = progs[fp]
            assert p["dispatches"] > 0
            assert p["modeled_flops_per_exec"] is not None, fp
            assert p["modeled_bytes_per_exec"] is not None, fp
            assert p["achieved_flops_s"] is not None, fp
            assert p["residual_ratio"] is not None, fp
        # pad-waste + rung accounting for the bucketed block program
        # (4100 rows / 8 blocks: the 513-row blocks pad to the 1024
        # rung)
        fused = max(rep["programs"], key=lambda p: p["dispatches"])
        assert fused["pad_rows"] > 0
        assert fused["bucket_rungs"], fused
        # device placements recorded (8-device conftest mesh)
        assert any(p["devices"] for p in rep["programs"])

    def test_text_rendering(self):
        ex = Executor()
        df = _frame(rows=1024, blocks=4)
        lf = _lazy_chain(df, ex)
        text = tfs.explain_analyze(lambda: _run_reduce(lf, ex))
        assert "explain_analyze:" in text
        assert "observed stages" in text
        assert "modeled" in text and "achieved" in text

    def test_lazy_frame_input_forces_fresh(self):
        ex = Executor()
        df = _frame(rows=512, blocks=4)
        lf = _lazy_chain(df, ex)
        lf.force()  # memoize — explain_analyze must still measure a run
        rep = tfs.explain_analyze(lf, format="json")
        assert any(p["dispatches"] > 0 for p in rep["programs"]), rep
        assert rep["plan"] is not None
        assert rep["plan"]["stages"][0]["verb"] == "map_blocks"

    def test_rejects_bad_inputs(self):
        df = _frame(rows=64, blocks=2)
        lf = _lazy_chain(df, Executor())
        with pytest.raises(TypeError, match="LazyPlan"):
            tfs.explain_analyze(lf.plan())
        with pytest.raises(TypeError, match="LazyFrame or a callable"):
            tfs.explain_analyze(df)
        with pytest.raises(ValueError, match="format"):
            tfs.explain_analyze(lf, format="yaml")

    def test_requires_telemetry(self):
        lf = _lazy_chain(_frame(rows=64, blocks=2), Executor())
        with config.override(telemetry=False):
            with pytest.raises(RuntimeError, match="telemetry"):
                tfs.explain_analyze(lf)


# ---------------------------------------------------------------------------
# WorkloadProfile
# ---------------------------------------------------------------------------


class TestWorkloadProfile:
    def test_save_load_round_trip_exact(self, tmp_path):
        ex = Executor()
        _lazy_chain(_frame(), ex).force()
        p1 = profiler.snapshot(note="run-1")
        path = str(tmp_path / "prof.json")
        p1.save(path)
        p2 = profiler.load(path)
        # save -> load is EXACT up to JSON canonicalization (tuples
        # become lists on the wire, so compare through one dump)
        assert p2.to_dict() == json.loads(json.dumps(p1.to_dict()))
        assert p2.meta["note"] == "run-1"
        assert p2.programs, "profile captured no programs"

    def test_rerun_diff_zero_structural_drift(self, tmp_path):
        """Acceptance: a profile saved from one run, loaded, and
        diffed against a second run of the same workload reports zero
        structural drift (same programs/rungs), only timing deltas."""
        ex = Executor()
        df = _frame()
        lf = _lazy_chain(df, ex, scale=7.25)
        _run_reduce(lf, ex)
        p1 = profiler.snapshot(note="run-1")
        path = str(tmp_path / "prof1.json")
        p1.save(path)

        # simulate a new process: wipe all in-memory measurement state,
        # then run the IDENTICAL workload again
        telemetry.reset()
        costmodel.reset()
        lf2 = _lazy_chain(df, ex, scale=7.25)
        _run_reduce(lf2, ex)
        p2 = profiler.snapshot(note="run-2")

        d = profiler.load(path).diff(p2)
        assert d["structural"] == [], d["structural"]
        assert not d["structural_drift"]
        # the runs are distinct executions: timing deltas exist (verb
        # seconds essentially never collide exactly)
        assert d["timing"], "expected timing deltas between two runs"
        # and the structural identity is real: program sets + rungs
        assert set(p1.programs) == set(p2.programs)
        for fp in p1.programs:
            assert p1.programs[fp]["rungs"] == p2.programs[fp]["rungs"]

    def test_diff_reports_structural_drift(self):
        ex = Executor()
        _lazy_chain(_frame(rows=512, blocks=2), ex).force()
        p1 = profiler.snapshot()
        telemetry.reset()
        costmodel.reset()
        # a DIFFERENT workload: new program + different block geometry
        ex2 = Executor()
        _lazy_chain(_frame(rows=300, blocks=3), ex2).force()
        p2 = profiler.snapshot()
        d = p1.diff(p2)
        assert d["structural_drift"]
        assert any("program" in s for s in d["structural"])

    def test_merge_sums_counters_and_hists(self):
        ex = Executor()
        _lazy_chain(_frame(rows=512, blocks=4), ex).force()
        p = profiler.snapshot()
        m = p.merge(p)
        for verb, v in p.verbs.items():
            assert m.verbs[verb]["calls"] == 2 * v["calls"]
            assert m.verbs[verb]["seconds"] == pytest.approx(
                2 * v["seconds"]
            )
            if v.get("latency"):
                assert m.verbs[verb]["latency"]["count"] == (
                    2 * v["latency"]["count"]
                )
        for fp in p.programs:
            assert m.programs[fp]["execs"] == 2 * p.programs[fp]["execs"]
            assert m.programs[fp]["rungs"] == p.programs[fp]["rungs"]

    def test_merge_refuses_mismatched_buckets(self):
        ex = Executor()
        _lazy_chain(_frame(rows=512, blocks=4), ex).force()
        p1 = profiler.snapshot()
        telemetry.reset()
        with config.override(
            histogram_buckets={"seconds": [0.5, 1.0, 2.0]}
        ):
            _lazy_chain(_frame(rows=512, blocks=4), Executor()).force()
            p2 = profiler.snapshot()
        with pytest.raises(ValueError, match="bucket"):
            p1.merge(p2)

    def test_schema_version_checked(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"schema": 999}))
        with pytest.raises(ValueError, match="schema"):
            profiler.load(str(path))

    def test_serving_ingest_admission_sections(self):
        # unit-level: the rollups aggregate the live counters the
        # serving/ingest/admission subsystems emit
        telemetry.counter_inc("serve_requests", 5.0, endpoint="ep1")
        telemetry.counter_inc("serve_batches", 2.0, endpoint="ep1")
        telemetry.counter_inc("serve_shed", 1.0, endpoint="ep1")
        telemetry.counter_inc("ingest_chunks", 4.0, stage="decode")
        telemetry.counter_inc(
            "ingest_stage_busy_seconds", 0.5, stage="decode"
        )
        telemetry.counter_inc(
            "ingest_stage_wait_seconds", 0.25, stage="decode"
        )
        telemetry.counter_inc("deadline_exceeded", 2.0, verb="map_blocks")
        p = profiler.snapshot().to_dict()
        assert p["serving"]["endpoints"]["ep1"] == {
            "requests": 5, "batches": 2, "shed": 1,
        }
        assert p["ingest"]["decode"]["busy_s"] == pytest.approx(0.5)
        assert p["ingest"]["decode"]["wait_s"] == pytest.approx(0.25)
        assert p["admission"]["deadline_exceeded"]["map_blocks"] == 2

    def test_cross_process_load_and_diff(self, tmp_path):
        """A profile saved here loads in a FRESH interpreter and diffs
        clean against itself — the artifact is genuinely portable."""
        ex = Executor()
        _lazy_chain(_frame(rows=512, blocks=4), ex).force()
        path = str(tmp_path / "prof.json")
        profiler.snapshot(note="parent").save(path)
        code = (
            "import jax; jax.config.update('jax_platforms','cpu');"
            "from tensorframes_tpu.runtime import profiler;"
            f"p = profiler.load({path!r});"
            "d = p.diff(p);"
            "assert not d['structural_drift'], d;"
            "assert p.meta['note'] == 'parent';"
            "print('CROSS_PROCESS_OK', len(p.programs))"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "CROSS_PROCESS_OK" in proc.stdout

    def test_profile_route(self):
        from tensorframes_tpu.utils import telemetry_http

        ex = Executor()
        _lazy_chain(_frame(rows=512, blocks=4), ex).force()
        srv = telemetry_http.serve(port=0)
        try:
            with urllib.request.urlopen(
                f"{srv.url}/profile", timeout=10
            ) as r:
                assert r.status == 200
                body = json.loads(r.read())
            assert body["schema"] == profiler.PROFILE_SCHEMA
            assert body["programs"], body.keys()
            assert "verbs" in body and "bucketing" in body
            with urllib.request.urlopen(f"{srv.url}/", timeout=10) as r:
                assert "/profile" in json.loads(r.read())["routes"]
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# cost-model residuals
# ---------------------------------------------------------------------------


def _fake_dispatch(fp, rows, seconds, n=1):
    """Fabricate a dispatched program: a ledger entry via note_exec
    (arg/out bytes captured from the concrete arrays) plus already-timed
    dispatch spans — the residual join's two inputs, minus jit."""
    args = [np.zeros((rows, 8), dtype=np.float32)]
    out = [np.zeros((rows, 8), dtype=np.float32)]
    for i in range(n):
        costmodel.note_exec(("block", fp), args, out)
        telemetry.add_event(
            f"fake.{fp}", "dispatch", 100.0 + i, 100.0 + i + seconds,
            program=fp, rows=rows,
        )


class TestResiduals:
    def test_relative_residual_fit(self):
        # two programs, same modeled bytes; B takes 9x longer -> the
        # fit splits the difference and the ratios straddle 1 at ~1:9
        _fake_dispatch("prog_a", 512, 0.010, n=4)
        _fake_dispatch("prog_b", 512, 0.090, n=4)
        res = costmodel.residuals()
        assert res["fit"]["bytes_per_s"] is not None
        ra = res["programs"]["prog_a"]["residual_ratio"]
        rb = res["programs"]["prog_b"]["residual_ratio"]
        assert ra < 1.0 < rb
        assert rb / ra == pytest.approx(9.0, rel=0.05)

    def test_flagging_threshold(self):
        # fit lands between them: ratios ~0.2 (a) and ~1.8 (b), so at
        # threshold 2.0 the FAST program is the flagged outlier
        # (0.2 < 1/2) while 1.8 stays inside the band
        _fake_dispatch("prog_a", 512, 0.010, n=4)
        _fake_dispatch("prog_b", 512, 0.090, n=4)
        with config.override(cost_residual_warn_ratio=2.0):
            res = costmodel.residuals()
            assert res["programs"]["prog_a"]["flagged"]
            assert not res["programs"]["prog_b"]["flagged"]
        with config.override(cost_residual_warn_ratio=0.0):
            res = costmodel.residuals()
            assert not any(
                p["flagged"] for p in res["programs"].values()
            )

    def test_diagnostics_accuracy_section(self):
        _fake_dispatch("prog_a", 512, 0.010, n=4)
        _fake_dispatch("prog_b", 512, 0.090, n=4)
        with config.override(cost_residual_warn_ratio=2.0):
            data = tfs.diagnostics(format="json")
            assert data["accuracy"]["programs"]["prog_a"]["flagged"]
            text = tfs.diagnostics()
            assert "cost-model accuracy" in text
            assert "MODEL MISPRICES" in text

    def test_real_chain_residuals_present(self):
        ex = Executor()
        lf = _lazy_chain(_frame(), ex)
        _run_reduce(lf, ex)
        res = costmodel.residuals()
        assert res["fit"]["groups"] > 0
        assert any(
            p["residual_ratio"] is not None
            for p in res["programs"].values()
        )

    def test_costmodel_residual_prometheus_family(self):
        _fake_dispatch("prog_a", 512, 0.010, n=4)
        _fake_dispatch('we"ird\\prog\n', 512, 0.030, n=4)
        text = telemetry.export_prometheus()
        lines = text.splitlines()
        idx = [
            i for i, l in enumerate(lines)
            if l.startswith("tfs_costmodel_residual{")
        ]
        assert idx, "costmodel_residual gauge family missing"
        # HELP precedes TYPE precedes samples
        help_i = lines.index(
            "# HELP tfs_costmodel_residual "
            "Span-achieved vs cost-model-predicted time ratio per program"
        )
        type_i = lines.index("# TYPE tfs_costmodel_residual gauge")
        assert help_i < type_i < idx[0]
        # label escaping survived the weird fingerprint
        assert any(
            'program="we\\"ird\\\\prog\\n"' in l for l in lines
        ), [l for l in lines if "costmodel_residual" in l]


# ---------------------------------------------------------------------------
# bucket-fill accounting
# ---------------------------------------------------------------------------


class TestBucketFill:
    def test_fill_histogram_per_verb(self):
        ex = Executor()
        df = _frame(rows=4100, blocks=8)  # 513-row blocks: pad to 1024
        tfs.map_blocks(
            (tfs.block(df, "x") * float(next(_UNIQ) + 2)).named("y"),
            df, executor=ex,
        )
        hists = telemetry.metrics_snapshot()[2]
        key = ("bucket_fill", (("verb", "map_blocks"),))
        assert key in hists, sorted(k for k in hists if k[0] == "bucket_fill")
        _b, _c, hsum, hcount = hists[key]
        assert hcount == 8
        assert 0.0 < hsum / hcount <= 1.0
        # pad-waste counters still live beside the fill fractions
        counters = telemetry.flat_counters()
        assert counters.get("shape_bucketing.pad_rows", 0) > 0

    def test_exact_rung_observes_full_fill(self):
        ex = Executor()
        df = _frame(rows=4096, blocks=8)  # 512-row blocks: exact rung
        # devices=: block by block (left to the scheduler, equal blocks of
        # a resident column are one group on its device: one observation)
        tfs.map_blocks(
            (tfs.block(df, "x") * float(next(_UNIQ) + 2)).named("y"),
            df, executor=ex, devices=jax.local_devices()[:4],
        )
        hists = telemetry.metrics_snapshot()[2]
        _b, _c, hsum, hcount = hists[("bucket_fill", (("verb", "map_blocks"),))]
        assert hcount == 8
        assert hsum == pytest.approx(8.0)  # every dispatch at fill 1.0

    def test_prometheus_exposition_with_inf_bucket(self):
        ex = Executor()
        df = _frame(rows=300, blocks=3)
        tfs.map_blocks(
            (tfs.block(df, "x") * float(next(_UNIQ) + 2)).named("y"),
            df, executor=ex,
        )
        text = telemetry.export_prometheus()
        lines = text.splitlines()
        help_i = lines.index(
            "# HELP tfs_bucket_fill "
            "Valid-row fraction of each bucketed dispatch by verb"
        )
        type_i = lines.index("# TYPE tfs_bucket_fill histogram")
        assert help_i < type_i
        inf = [
            l for l in lines
            if l.startswith("tfs_bucket_fill_bucket")
            and 'le="+Inf"' in l
        ]
        assert inf and 'verb="map_blocks"' in inf[0]
        assert any(l.startswith("tfs_bucket_fill_count") for l in lines)

    def test_diagnostics_pad_waste_line(self):
        ex = Executor()
        whole = _frame(rows=4106, blocks=1)
        df = tfs.TensorFrame(
            [whole["x"]],
            [int(v) for v in np.cumsum(
                [0, 513, 512, 514, 512, 515, 512, 516, 512]
            )],
        )
        tfs.map_blocks(
            (tfs.block(df, "x") * float(next(_UNIQ) + 2)).named("y"),
            df, executor=ex,
        )
        data = tfs.diagnostics(format="json")
        bk = data["bucketing"]
        # a resident frame of several blocks, no two neighbours of one
        # size (a run of equal blocks is one group, not windows): each
        # block off its rung (513 to 516 rows; the 512-row ones are on
        # theirs) is a window of the column
        # (`shape_policy.block_feeds`), none a replicated pad
        assert bk["window_dispatches"] == 4 and bk["padded_dispatches"] == 0
        assert bk["pad_rows"] > 0
        assert 0.0 < bk["fill"]["map_blocks"]["mean"] <= 1.0
        text = tfs.diagnostics()
        assert "bucketing:" in text and "pad row" in text
        assert "4 window dispatch(es)" in text

    def test_disabled_telemetry_skips_fill(self):
        ex = Executor()
        df = _frame(rows=300, blocks=3)
        with config.override(telemetry=False):
            tfs.map_blocks(
                (tfs.block(df, "x") * float(next(_UNIQ) + 2)).named("y"),
                df, executor=ex,
            )
        hists = telemetry.metrics_snapshot()[2]
        assert not any(k[0] == "bucket_fill" for k in hists)


# ---------------------------------------------------------------------------
# histogram bucket overrides
# ---------------------------------------------------------------------------


class TestHistogramBuckets:
    DEFAULT_SECONDS = (
        1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
        1e-1, 2.5e-1, 5e-1, 1.0, 2.5, 5.0, 10.0, 30.0,
    )

    def test_defaults_byte_identical(self):
        telemetry.histogram_observe("verb_seconds", 0.01, verb="v")
        hists = telemetry.metrics_snapshot()[2]
        buckets = hists[("verb_seconds", (("verb", "v"),))][0]
        assert tuple(buckets) == self.DEFAULT_SECONDS

    def test_override_by_family(self):
        with config.override(
            histogram_buckets={"seconds": [0.001, 0.005, 0.02]}
        ):
            telemetry.histogram_observe("verb_seconds", 0.01, verb="v")
            hists = telemetry.metrics_snapshot()[2]
            buckets, counts, _s, _c = hists[
                ("verb_seconds", (("verb", "v"),))
            ]
            assert tuple(buckets) == (0.001, 0.005, 0.02)
            assert counts[2] == 1  # 0.01 lands in (0.005, 0.02]

    def test_override_by_name_wins_over_family(self):
        with config.override(
            histogram_buckets={
                "seconds": [1.0, 2.0],
                "verb_seconds": [0.1, 0.2, 0.3],
            }
        ):
            telemetry.histogram_observe("verb_seconds", 0.15, verb="v")
            telemetry.histogram_observe("compile_seconds", 1.5)
            hists = telemetry.metrics_snapshot()[2]
            assert tuple(
                hists[("verb_seconds", (("verb", "v"),))][0]
            ) == (0.1, 0.2, 0.3)
            assert tuple(hists[("compile_seconds", ())][0]) == (1.0, 2.0)

    def test_existing_series_keep_their_ladder(self):
        telemetry.histogram_observe("verb_seconds", 0.01, verb="v")
        with config.override(
            histogram_buckets={"seconds": [0.5, 1.0]}
        ):
            telemetry.histogram_observe("verb_seconds", 0.01, verb="v")
            hists = telemetry.metrics_snapshot()[2]
            buckets, _c, _s, count = hists[
                ("verb_seconds", (("verb", "v"),))
            ]
            assert tuple(buckets) == self.DEFAULT_SECONDS
            assert count == 2

    def test_malformed_override_falls_back(self):
        for bad in (
            {"seconds": [3.0, 1.0]},  # not ascending
            {"seconds": []},
            {"seconds": "nope"},
        ):
            with config.override(histogram_buckets=bad):
                telemetry.reset()
                telemetry.histogram_observe("verb_seconds", 0.01, verb="v")
                hists = telemetry.metrics_snapshot()[2]
                assert tuple(
                    hists[("verb_seconds", (("verb", "v"),))][0]
                ) == self.DEFAULT_SECONDS
            telemetry.reset()

    def test_serving_histograms_on_rows_ladder(self):
        # regression: serve_batch_rows/serve_batch_fill previously fell
        # to the implicit "seconds" ladder (top 30), parking every real
        # count in the +Inf overflow bucket — quantiles unreadable
        telemetry.histogram_observe("serve_batch_rows", 256.0)
        telemetry.histogram_observe("serve_batch_fill", 4.0)
        hists = telemetry.metrics_snapshot()[2]
        rows_ladder = (
            1.0, 8.0, 64.0, 512.0, 4096.0, 32768.0, 262144.0,
            2097152.0, 16777216.0, 134217728.0, 1073741824.0,
        )
        for name in ("serve_batch_rows", "serve_batch_fill"):
            buckets, counts, _s, _c = hists[(name, ())]
            assert tuple(buckets) == rows_ladder, name
            assert counts[-1] == 0, f"{name} landed in +Inf"

    def test_env_seeding(self, monkeypatch):
        from tensorframes_tpu.config import _env_histogram_buckets

        monkeypatch.setenv(
            "TFS_HISTOGRAM_BUCKETS", '{"seconds": [0.001, 0.01]}'
        )
        assert _env_histogram_buckets() == {"seconds": [0.001, 0.01]}
        monkeypatch.setenv("TFS_HISTOGRAM_BUCKETS", "not json{")
        assert _env_histogram_buckets() is None
        monkeypatch.delenv("TFS_HISTOGRAM_BUCKETS")
        assert _env_histogram_buckets() is None


# ---------------------------------------------------------------------------
# one clock: record() == span seconds == histogram
# ---------------------------------------------------------------------------


class TestRecordSingleClock:
    def test_span_histogram_and_counter_agree_exactly(self):
        import time

        from tensorframes_tpu.utils.profiling import record

        with record("clocktest", 100):
            time.sleep(0.01)
        span = next(
            s for s in telemetry.spans() if s.name == "clocktest"
        )
        hists = telemetry.metrics_snapshot()[2]
        _b, _c, hsum, hcount = hists[
            ("verb_seconds", (("verb", "clocktest"),))
        ]
        counters = telemetry.flat_counters()
        # EXACT equality: one perf_counter pair feeds all three
        assert hcount == 1
        assert hsum == span.seconds
        assert counters["clocktest.seconds"] == span.seconds
        assert counters["clocktest.calls"] == 1

    def test_disabled_telemetry_still_counts(self):
        from tensorframes_tpu.utils.profiling import record

        with config.override(telemetry=False):
            with record("offclock", 10):
                pass
            counters = telemetry.flat_counters()
            assert counters["offclock.calls"] == 1
            assert counters["offclock.seconds"] >= 0.0


# ---------------------------------------------------------------------------
# tools/profile_report.py
# ---------------------------------------------------------------------------


class TestProfileReport:
    def _tool(self):
        import importlib.util

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "profile_report", os.path.join(root, "tools", "profile_report.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _saved(self, tmp_path, name="p.json"):
        ex = Executor()
        lf = _lazy_chain(_frame(rows=1025, blocks=4), ex)
        _run_reduce(lf, ex)
        path = str(tmp_path / name)
        profiler.snapshot(note="report-test").save(path)
        return path

    def test_render(self, tmp_path, capsys):
        tool = self._tool()
        path = self._saved(tmp_path)
        assert tool.main([path]) == 0
        out = capsys.readouterr().out
        assert "workload profile" in out
        assert "programs (cost ledger):" in out
        assert "verbs:" in out

    def test_json_mode(self, tmp_path, capsys):
        tool = self._tool()
        path = self._saved(tmp_path)
        assert tool.main([path, "--json"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert data["schema"] == profiler.PROFILE_SCHEMA

    def test_self_diff_clean(self, tmp_path, capsys):
        tool = self._tool()
        path = self._saved(tmp_path)
        assert tool.main([path, "--diff", path, "--fail-on-drift"]) == 0
        assert "structural drift: none" in capsys.readouterr().out

    def test_drift_exit_code(self, tmp_path, capsys):
        tool = self._tool()
        a = self._saved(tmp_path, "a.json")
        telemetry.reset()
        costmodel.reset()
        ex = Executor()
        _lazy_chain(_frame(rows=300, blocks=3), ex).force()
        b = str(tmp_path / "b.json")
        profiler.snapshot().save(b)
        assert tool.main([a, "--diff", b]) == 0  # report-only by default
        assert tool.main([a, "--diff", b, "--fail-on-drift"]) == 2
        assert "STRUCTURAL DRIFT" in capsys.readouterr().out
