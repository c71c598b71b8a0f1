"""Always-on cost/memory introspection, OOM forensics, live telemetry
endpoint (ISSUE 8).

Covers the program cost ledger (`runtime.costmodel`): capture at
compile time on both the jit path and disabled states, exact per-shape
execution counting, per-verb footprint high-water marks, the roofline
join surfaced through ``tfs.diagnostics(format="json")``; OOM
forensics (`runtime.faults.record_oom`): snapshots in
``executor_stats()["faults"]["forensics"]`` naming program / modeled
footprint / split decision for split and re-raise paths; the HTTP
endpoint (`utils.telemetry_http`): all four routes, concurrent-scrape
consistency during a scheduled multi-device run, health degradation.
"""

import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import config
from tensorframes_tpu import dsl
from tensorframes_tpu.runtime import costmodel
from tensorframes_tpu.runtime import faults as rt_faults
from tensorframes_tpu.runtime.executor import Executor
from tensorframes_tpu.runtime.scheduler import device_health
from tensorframes_tpu.testing import faults as chaos
from tensorframes_tpu.utils import telemetry
from tensorframes_tpu.utils import telemetry_http
from tensorframes_tpu.utils.inspection import executor_stats

import jax


def _frame(rows=4096, blocks=8):
    return tfs.TensorFrame.from_dict(
        {"x": np.arange(rows, dtype=np.float32)}, num_blocks=blocks
    ).to_device()


def _block_by_block():
    """``devices=`` for a map over `_frame()`: the caller's placement,
    block for block. Left to the scheduler, a row-local map of these
    resident equal blocks is ONE dispatch on the column's device (the
    home plan and the block group), not one a block."""
    return jax.local_devices()[:4]


def _chained_lazy(df, executor=None):
    lf = df.lazy().map_blocks(
        (tfs.block(df, "x") * 2.0 + 1.0).named("y"), executor=executor
    )
    return lf.reduce_blocks(
        dsl.reduce_sum(
            tfs.block(lf, "y", tf_name="y_input"), axes=[0]
        ).named("y"),
        executor=executor,
    )


# ---------------------------------------------------------------------------
# the cost ledger
# ---------------------------------------------------------------------------


class TestCostLedger:
    def test_chained_lazy_reports_cost_for_every_program(self):
        """Acceptance: on a chained lazy map→reduce, diagnostics
        reports flops, HBM bytes, footprint and achieved-vs-peak
        fields for every cached program fingerprint, with >= 95% of
        wall time attributed."""
        ex = Executor()
        df = _frame()
        out = _chained_lazy(df, executor=ex)
        jax.block_until_ready(out)

        diag = tfs.diagnostics(ex, format="json")
        assert diag["window"]["coverage"] >= 0.95, diag["window"]

        cached_fps = {str(k[1]) for k in ex.cache_keys()}
        assert cached_fps, "lazy chain cached no programs"
        rows = {r["program"]: r for r in diag["cost"]["programs"]}
        for fp in cached_fps:
            assert fp in rows, f"program {fp} missing from the cost ledger"
            r = rows[fp]
            assert r["execs"] > 0
            assert r["flops_per_exec"] is not None, f"{fp}: no flops"
            assert r["bytes_per_exec"] is not None, f"{fp}: no HBM bytes"
            assert r["footprint_bytes"], f"{fp}: no footprint"
            # cpu has no datasheet peak: achieved rates computed, the
            # peak fractions honestly absent
            assert r["achieved_flops_s"] is not None
            assert r["achieved_hbm_bytes_s"] is not None
            assert r["flops_frac_of_peak"] is None
        # the rendered report carries the same table
        text = tfs.diagnostics(ex)
        assert "cost ledger" in text

    def test_exec_counts_are_exact(self):
        df = _frame(rows=512, blocks=4)
        z = (tfs.block(df, "x") * 3.0).named("y")
        four = _block_by_block()
        tfs.map_blocks(z, df, devices=four)  # warm: compiles + first 4 execs
        before = {
            fp: c["execs"] for fp, c in costmodel.program_costs().items()
        }
        tfs.map_blocks(z, df, devices=four)
        after = costmodel.program_costs()
        grew = {
            fp: after[fp]["execs"] - before.get(fp, 0)
            for fp in after
            if after[fp]["execs"] != before.get(fp, 0)
        }
        # 4 equal-size blocks bucket to one shape: exactly 4 new execs
        assert sum(grew.values()) == 4, grew

    def test_verb_peak_high_water(self):
        df = _frame(rows=2048, blocks=4)
        tfs.map_blocks((tfs.block(df, "x") * 2.0).named("y"), df)
        peaks = costmodel.verb_peaks()
        assert "map_blocks" in peaks
        pk = peaks["map_blocks"]
        assert pk["bytes"] > 0 and pk["program"] and pk["rows"]

    def test_disabled_ledger_captures_nothing(self):
        costmodel.reset()
        df = _frame(rows=256, blocks=2)
        with config.override(cost_ledger=False):
            tfs.map_blocks((tfs.block(df, "x") + 7.0).named("y"), df)
            assert costmodel.program_costs() == {}

    def test_deep_capture_fills_temp_bytes(self):
        df = _frame(rows=333, blocks=1)
        with config.override(cost_ledger_memory=True):
            # a fresh constant => fresh fingerprint => fresh compile
            tfs.map_blocks((tfs.block(df, "x") * 7.125).named("y"), df)
        deep = [
            c for c in costmodel.program_costs().values() if c["temp_known"]
        ]
        assert deep, "cost_ledger_memory=True captured no temp bytes"

    def test_roofline_fractions_with_known_peak(self, monkeypatch):
        df = _frame(rows=512, blocks=2)
        out = tfs.map_blocks(
            (tfs.block(df, "x") * 0.5).named("y"), df,
            devices=_block_by_block(),
        )
        jax.block_until_ready(out["y"].values)
        kind = costmodel.device_peaks()["device_kind"]
        monkeypatch.setitem(
            costmodel.DEVICE_PEAKS,
            kind,
            {"hbm_bytes_s": 1e9, "matmul_flops_s": 1e12},
        )
        agg = telemetry.span_aggregates()
        rows = [
            r for r in costmodel.roofline(agg["by_program"]) if r["execs"]
        ]
        assert rows
        with_frac = [r for r in rows if r["flops_frac_of_peak"] is not None]
        assert with_frac, "known peak produced no fraction"
        for r in with_frac:
            assert r["flops_frac_of_peak"] > 0
            assert r["hbm_frac_of_peak"] is not None

    def test_memory_overview_per_device(self):
        rows = costmodel.memory_overview()
        assert len(rows) >= 1
        for r in rows:
            assert re.match(r"^\w+:\d+$", r["device"])
            assert isinstance(r["live_buffer_bytes"], int)
            assert isinstance(r["live_buffers"], int)
            # CPU backend reports no memory_stats: honest None
            assert r["bytes_in_use"] is None or r["bytes_in_use"] >= 0

    def test_device_memory_gauges_exported(self):
        df = _frame(rows=64, blocks=1)
        jax.block_until_ready(df.column("x").values)
        text = telemetry.export_prometheus()
        assert "tfs_live_buffer_bytes{device=" in text


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------


class TestOomForensics:
    def test_injected_resource_produces_snapshot(self):
        """Acceptance: an injected RESOURCE_EXHAUSTED dispatch produces
        a forensic snapshot in executor_stats()["faults"] naming the
        program, its modeled footprint, and the split decision."""
        df = _frame(rows=2048, blocks=4)
        z = (tfs.block(df, "x") * 2.0 + 1.0).named("y")
        four = _block_by_block()
        ref = np.asarray(tfs.map_blocks(z, df, devices=four)["y"].values)
        with chaos.inject(nth=[1], fault="resource") as plan:
            got = np.asarray(tfs.map_blocks(z, df, devices=four)["y"].values)
        assert plan.injected == 1
        np.testing.assert_array_equal(ref, got)

        fl = executor_stats()["faults"]
        assert fl["splits"] >= 1
        snaps = fl["forensics"]
        assert snaps, "no forensic snapshot for the injected OOM"
        snap = snaps[0]
        assert snap["verb"] == "map_blocks"
        assert snap["program"]  # the failing program is named
        assert snap["decision"].startswith("split:")
        assert snap["rows"] > 0 and snap["depth"] == 0
        assert snap["modeled"]["footprint_bytes"] > 0
        assert snap["devices"], "no per-device memory in the snapshot"
        assert snap["error"].startswith("InjectedFault")
        # and diagnostics renders it
        assert "oom[map_blocks]" in tfs.diagnostics()

    def test_depth_exhausted_records_reraise_decision(self):
        df = _frame(rows=1024, blocks=2)
        z = (tfs.block(df, "x") + 1.0).named("y")
        four = _block_by_block()
        tfs.map_blocks(z, df, devices=four)  # warm: the ledger knows the program
        with config.override(oom_split_depth=0):
            with chaos.inject(nth=[0], fault="resource"):
                with pytest.raises(chaos.InjectedFault):
                    tfs.map_blocks(z, df, devices=four)
        snaps = rt_faults.forensics_snapshot()
        assert snaps and snaps[-1]["decision"] == (
            "reraise:split-depth-exhausted"
        )

    def test_forensics_log_is_bounded(self):
        err = RuntimeError("RESOURCE_EXHAUSTED: synthetic")
        for i in range(40):
            rt_faults.record_oom("v", f"prog{i}", 10, 0, "split:x", err)
        assert len(rt_faults.forensics_snapshot()) == 16

    def test_reset_clears_forensics(self):
        err = RuntimeError("RESOURCE_EXHAUSTED: synthetic")
        rt_faults.record_oom("v", "p", 10, 0, "split:x", err)
        assert rt_faults.forensics_snapshot()
        rt_faults.reset_ledger()
        assert rt_faults.forensics_snapshot() == []

    def test_snapshot_counter_live(self):
        err = RuntimeError("RESOURCE_EXHAUSTED: synthetic")
        rt_faults.record_oom("averb", "p", 10, 1, "split:x", err)
        flat = telemetry.flat_counters()
        assert flat.get('oom_forensics{verb=averb}') == 1.0


# ---------------------------------------------------------------------------
# the live endpoint
# ---------------------------------------------------------------------------

_METRIC_RE = re.compile(
    r"^[A-Za-z_:][A-Za-z0-9_:]*(\{.*\})? [0-9eE+.\-]+$"
)


def _get(url, route):
    with urllib.request.urlopen(url + route, timeout=10) as r:
        return r.status, r.read().decode()


def _assert_valid_prometheus(text):
    for line in text.splitlines():
        if not line.strip() or line.startswith("#"):
            continue
        assert _METRIC_RE.match(line), f"bad exposition line: {line!r}"


class TestEndpoint:
    def test_routes(self):
        srv = telemetry.serve(port=0)
        try:
            df = _frame(rows=1024, blocks=4)
            jax.block_until_ready(_chained_lazy(df))
            code, metrics = _get(srv.url, "/metrics")
            assert code == 200
            _assert_valid_prometheus(metrics)
            assert "# HELP" in metrics and "# TYPE" in metrics
            code, body = _get(srv.url, "/healthz")
            assert code == 200
            h = json.loads(body)
            assert h["status"] == "ok" and not h["degraded"]
            assert len(h["devices"]) == len(jax.local_devices())
            code, body = _get(srv.url, "/diagnostics")
            assert code == 200
            d = json.loads(body)
            assert d["window"]["spans"] >= 0 and "cost" in d
            code, body = _get(srv.url, "/trace")
            assert code == 200
            assert json.loads(body)["traceEvents"]
            # unknown route: 404, not a crash
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url, "/nope")
            assert ei.value.code == 404
        finally:
            srv.close()

    def test_concurrent_scrapes_during_scheduled_run(self):
        """Acceptance: serve() under 8 concurrent scrape threads during
        a scheduled multi-device run returns valid Prometheus text and
        consistent JSON diagnostics — no torn reads, no exceptions."""
        srv = telemetry.serve(port=0)
        errors = []
        stop = threading.Event()

        def scraper(i):
            routes = ("/metrics", "/diagnostics", "/healthz", "/trace")
            k = 0
            try:
                while not stop.is_set() or k < 3:
                    code, body = _get(srv.url, routes[k % 4])
                    assert code == 200
                    if k % 4 == 0:
                        _assert_valid_prometheus(body)
                    else:
                        json.loads(body)
                    k += 1
                    if k >= 40:
                        break
            except Exception as e:  # pragma: no cover - the assertion
                errors.append((i, repr(e)))

        threads = [
            threading.Thread(target=scraper, args=(i,)) for i in range(8)
        ]
        try:
            for t in threads:
                t.start()
            # the scheduled multi-device run under scrape load (conftest
            # forces 8 virtual CPU devices; auto-scheduling is on)
            df = _frame(rows=8192, blocks=16)
            z = (tfs.block(df, "x") * 2.0 + 1.0).named("y")
            for _ in range(4):
                mapped = tfs.map_blocks(z, df)
                s = tfs.reduce_blocks(
                    dsl.reduce_sum(
                        tfs.block(mapped, "y", tf_name="y_input"), axes=[0]
                    ).named("y"),
                    mapped,
                )
                jax.block_until_ready(s)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
            srv.close()
        assert not errors, errors
        assert not any(t.is_alive() for t in threads)

    def test_serve_is_process_wide(self):
        srv = telemetry.serve(port=0)
        try:
            again = telemetry.serve(port=0)
            assert again is srv
            with pytest.raises(RuntimeError):
                telemetry.serve(port=srv.port + 1)
        finally:
            srv.close()
        assert telemetry_http.active_server() is None

    def test_healthz_degraded_on_open_circuit(self):
        srv = telemetry.serve(port=0)
        try:
            device_health().mark_failure("cpu:0")
            _, body = _get(srv.url, "/healthz")
            h = json.loads(body)
            assert h["degraded"] and h["status"] == "degraded"
            states = {r["device"]: r["state"] for r in h["devices"]}
            assert states["cpu:0"] == "open"
        finally:
            srv.close()
            device_health().reset()

    def test_serve_without_port_or_config_raises(self):
        with pytest.raises(ValueError):
            telemetry.serve()

    def test_maybe_serve_off_is_noop(self):
        assert telemetry.maybe_serve() is None
        assert telemetry_http.active_server() is None

    def test_maybe_serve_starts_from_config(self):
        with config.override(telemetry_port=0):
            # port=0 is "off" for maybe_serve (the default state)
            assert telemetry.maybe_serve() is None
        srv = None
        try:
            probe = telemetry_http.TelemetryServer("127.0.0.1", 0)
            free = probe.port
            probe.close()
            with config.override(telemetry_port=free):
                srv = telemetry.maybe_serve()
                assert srv is not None and srv.port == free
        finally:
            if srv is not None:
                srv.close()
