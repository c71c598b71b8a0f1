"""Incident flight recorder: fault-triggered postmortem bundles
(ISSUE 19).

The acceptance contracts under test:

- THE acceptance case: a chained lazy map→reduce with an injected
  ``nth=[0]`` hang and a 0.4s budget trips `DeadlineExceeded` AND
  leaves exactly one bundle whose rendered postmortem names the verb,
  the budget, the offending program fingerprint, and the blocks
  issued/unissued split — loadable bit-identically in a fresh
  interpreter via ``tools/postmortem.py``.
- A 2× overload burst produces exactly ONE shed bundle with
  ``incidents_suppressed{reason="rate_limit"}`` counting the rest.
- ``/healthz`` and ``/metrics`` answer while a bundle is mid-write (no
  lock across file I/O), and a full store (0-byte quota) degrades to a
  counted ``incidents_suppressed{reason="store"}`` — never an
  exception on the caller's fault path.
- Every trigger class reports through the one choke point: deadline,
  shed, eviction, OOM exhaustion, checkpoint corruption, serving 5xx.
- Satellites: atomic `export_chrome_trace(path=)` (no torn reads), the
  always-live ``spans_dropped`` gauge.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import config, dsl
from tensorframes_tpu.frame import TensorFrame
from tensorframes_tpu.runtime import blackbox
from tensorframes_tpu.runtime import checkpoint as ckpt
from tensorframes_tpu.runtime import deadline as dl
from tensorframes_tpu.runtime import faults as rtf
from tensorframes_tpu.runtime.scheduler import device_health
from tensorframes_tpu.testing import faults as chaos
from tensorframes_tpu.utils import telemetry, telemetry_http

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_POSTMORTEM = os.path.join(_REPO, "tools", "postmortem.py")


def _frame(n=128, blocks=4, seed=3):
    rng = np.random.RandomState(seed)
    return TensorFrame.from_dict(
        {"x": rng.rand(n).astype(np.float32)}, num_blocks=blocks
    )


def _double(df):
    return (tfs.block(df, "x") * 2.0 + 1.0).named("y")


def _chain(frame, **kw):
    lz = frame.lazy().map_blocks(_double(frame))
    fetch = dsl.reduce_sum(
        tfs.block(lz, "y", tf_name="y_input"), axes=[0]
    ).named("y")
    return tfs.reduce_blocks(fetch, lz, **kw)


def _get(url, route):
    with urllib.request.urlopen(url + route, timeout=10) as r:
        return r.status, r.read().decode()


# ---------------------------------------------------------------------------
# THE acceptance case
# ---------------------------------------------------------------------------


class TestAcceptance:
    def test_chained_lazy_hang_leaves_one_postmortem_bundle(self, tmp_path):
        df = _frame()
        with config.override(incident_dir=str(tmp_path)):
            ref = float(np.asarray(_chain(df)))  # warm, fault-free: no bundle
            assert tfs.incidents() == []
            with chaos.inject(nth=[0], fault="hang", delay_s=30.0):
                with pytest.raises(dl.DeadlineExceeded) as ei:
                    _chain(df, timeout_s=0.4)
            # exactly one bundle, stamped onto the escaping exception
            rows = tfs.incidents()
            assert len(rows) == 1
            iid = rows[0]["id"]
            assert ei.value.tfs_incident_id == iid
            assert rows[0]["trigger"] == "deadline"
            bundle = tfs.incidents(iid)

        # the bundle names the verb, the budget, the offending program
        # and the partial-work split
        assert bundle["verb"] == "reduce_blocks"
        assert bundle["fault"]["type"] == "DeadlineExceeded"
        assert abs(bundle["fault"]["budget_s"] - 0.4) < 0.05
        assert bundle["fault"]["blocks_issued"] is not None
        assert bundle["fault"]["blocks_unissued"] is not None
        prog = bundle["program"]["fingerprint"]
        assert prog
        # joined with the cost ledger + residual at capture time
        assert bundle["program"]["cost"] is not None
        assert bundle["trace"]["traceEvents"]
        assert bundle["config"]["digest"]
        assert isinstance(bundle["scheduler"]["admission"], dict)

        # rendered postmortem (fresh interpreters) names all four, and
        # --json round-trips BIT-IDENTICALLY
        path = rows[0]["path"]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        text = subprocess.run(
            [sys.executable, _POSTMORTEM, path],
            capture_output=True, env=env, timeout=120, check=True,
        ).stdout.decode()
        assert "reduce_blocks" in text
        assert "budget 0.400s" in text
        assert prog in text
        assert "issued" in text and "unissued" in text
        raw = [
            subprocess.run(
                [sys.executable, _POSTMORTEM, path, "--json"],
                capture_output=True, env=env, timeout=120, check=True,
            ).stdout
            for _ in range(2)
        ]
        assert raw[0] == raw[1]
        assert json.loads(raw[0].decode()) == bundle

        # the same executor runs clean afterwards
        with config.override(incident_dir=str(tmp_path)):
            assert float(np.asarray(_chain(df))) == ref

    def test_fault_free_runs_capture_nothing_armed_or_disarmed(
        self, tmp_path
    ):
        df = _frame()
        with config.override(incident_dir=str(tmp_path)):
            ref = float(np.asarray(_chain(df)))
            for _ in range(3):
                assert float(np.asarray(_chain(df))) == ref
                with config.override(incident_capture=False):
                    assert float(np.asarray(_chain(df))) == ref
            assert tfs.incidents() == []
        assert blackbox.state()["captured"] == 0

    def test_deadline_storm_without_dedup_bundles_every_fault(
        self, tmp_path
    ):
        """With the rate limit off, each of a burst of hung, deadlined
        verbs writes its own bundle and observes one capture latency;
        the gate drains and the next call is bit-identical."""
        df = _frame()
        ref = float(np.asarray(_chain(df)))
        storm = 3
        hits = 0
        with config.override(
            incident_dir=str(tmp_path), incident_rate_limit_s=0.0
        ):
            with chaos.inject(rate=1.0, seed=1, fault="hang", delay_s=30.0):
                for _ in range(storm):
                    try:
                        _chain(df, timeout_s=0.05)
                    except tfs.DeadlineExceeded:
                        hits += 1
            assert hits == storm
            assert len(tfs.incidents()) == storm
        assert blackbox.state()["captured"] == storm
        _c, _g, hists = telemetry.metrics_snapshot()
        assert hists[("incident_capture_seconds", ())][3] == storm
        assert dl.controller().in_flight_now() == 0
        assert float(np.asarray(_chain(df))) == ref

    def test_overload_burst_one_bundle_rest_suppressed(self, tmp_path):
        df = _frame()
        _chain(df)  # warm so every burst call sheds at admission
        release = dl.controller().admit("holder", None)
        sheds = 6
        try:
            with config.override(
                incident_dir=str(tmp_path),
                max_concurrent_verbs=1,
                admission_queue_limit=0,
            ):
                for _ in range(sheds):
                    with pytest.raises(tfs.OverloadError):
                        tfs.map_blocks(_double(df), df)
                rows = tfs.incidents()
                assert len(rows) == 1
                assert rows[0]["trigger"] == "shed"
                assert rows[0]["suppressed_since"] == sheds - 1
                bundle = tfs.incidents(rows[0]["id"])
        finally:
            release()
        flat = telemetry.flat_counters()
        assert flat.get("incidents_captured{trigger=shed}", 0) == 1
        assert (
            flat.get("incidents_suppressed{reason=rate_limit}", 0)
            == sheds - 1
        )
        assert bundle["fault"]["type"] == "OverloadError"
        assert bundle["fault"]["queue_depth"] is not None


# ---------------------------------------------------------------------------
# liveness + degradation (satellite 3)
# ---------------------------------------------------------------------------


class TestLivenessAndDegradation:
    def test_http_answers_while_bundle_mid_write(
        self, tmp_path, monkeypatch
    ):
        """No lock across file I/O: scrapes stay fast while a capture
        is stuck inside its store commit."""
        in_write = threading.Event()
        real_commit = ckpt.CheckpointStore.commit

        def slow_commit(self, manifest, payload):
            in_write.set()
            time.sleep(1.5)
            return real_commit(self, manifest, payload)

        monkeypatch.setattr(ckpt.CheckpointStore, "commit", slow_commit)
        srv = telemetry_http.serve(port=0)
        try:
            with config.override(incident_dir=str(tmp_path)):
                t = threading.Thread(
                    target=blackbox.capture, args=("deadline",)
                )
                t.start()
                assert in_write.wait(timeout=10)
                for route in ("/healthz", "/metrics"):
                    t0 = time.monotonic()
                    code, _body = _get(srv.url, route)
                    assert code == 200
                    assert time.monotonic() - t0 < 1.0, route
                t.join(timeout=30)
                assert not t.is_alive()
                assert len(tfs.incidents()) == 1
        finally:
            telemetry_http.shutdown()

    def test_full_store_degrades_to_counted_suppression(self, tmp_path):
        """ENOSPC simulated via a 0-byte quota: the typed fault still
        escapes cleanly and the drop is counted, not raised."""
        df = _frame()
        with config.override(
            incident_dir=str(tmp_path), incident_max_bytes=0
        ):
            with chaos.inject(nth=[0], fault="hang", delay_s=30.0):
                with pytest.raises(dl.DeadlineExceeded):
                    _chain(df, timeout_s=0.3)
            assert tfs.incidents() == []
        assert os.listdir(tmp_path) == []
        st = blackbox.state()
        assert st["captured"] == 0
        assert st["suppressed"].get("store", 0) >= 1
        flat = telemetry.flat_counters()
        assert flat.get("incidents_suppressed{reason=store}", 0) >= 1

    def test_unwritable_dir_degrades_not_raises(self, tmp_path):
        # a regular FILE where the store directory should be: mkdir and
        # the commit both fail (unlike chmod, this binds even for root)
        not_a_dir = tmp_path / "occupied"
        not_a_dir.write_text("not a directory")
        with config.override(incident_dir=str(not_a_dir)):
            assert blackbox.capture("deadline") is None
        assert blackbox.state()["suppressed"].get("store", 0) >= 1

    def test_disarmed_recorder_is_a_noop(self, tmp_path):
        with config.override(
            incident_dir=str(tmp_path), incident_capture=False
        ):
            assert blackbox.capture("deadline") is None
        assert os.listdir(tmp_path) == []
        assert blackbox.state()["captured"] == 0


# ---------------------------------------------------------------------------
# trigger classification: every escape hatch reports through the choke point
# ---------------------------------------------------------------------------


class TestTriggers:
    def test_eviction_capture(self, tmp_path):
        with config.override(incident_dir=str(tmp_path)):
            device_health().mark_failure("cpu:7")
            rows = tfs.incidents()
            assert len(rows) == 1
            assert rows[0]["trigger"] == "eviction"
            bundle = tfs.incidents(rows[0]["id"])
            assert bundle["extra"]["device"] == "cpu:7"
            assert bundle["extra"]["failures"] == 1
            # a flapping device rate-limits instead of flooding
            device_health().mark_failure("cpu:7")
            assert len(tfs.incidents()) == 1
        assert blackbox.state()["suppressed"].get("rate_limit", 0) >= 1

    def test_checkpoint_corruption_capture(self, tmp_path):
        victim = tmp_path / "stream.ckpt"
        victim.write_bytes(b"definitely not a checkpoint")
        with config.override(incident_dir=str(tmp_path / "incidents")):
            with pytest.raises(ckpt.CheckpointError) as ei:
                ckpt.CheckpointStore(str(victim)).load()
            rows = tfs.incidents()
            assert len(rows) == 1
            assert rows[0]["trigger"] == "checkpoint"
            assert ei.value.tfs_incident_id == rows[0]["id"]
            bundle = tfs.incidents(rows[0]["id"])
            assert bundle["fault"]["kind"] == "corrupt"

    def test_oom_split_exhaustion_capture(self, tmp_path):
        err = RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        with config.override(incident_dir=str(tmp_path)):
            rtf.record_oom(
                "map_blocks", "prog-fp-123", 4096, 3,
                "reraise:max_split_depth", err,
            )
            rows = tfs.incidents()
            assert len(rows) == 1
            assert rows[0]["trigger"] == "oom"
            bundle = tfs.incidents(rows[0]["id"])
            assert bundle["program"]["fingerprint"] == "prog-fp-123"
            assert (
                bundle["extra"]["oom"]["decision"]
                == "reraise:max_split_depth"
            )
            # a split decision is NOT an incident (the runtime recovers)
            rtf.record_oom(
                "map_blocks", "prog-fp-456", 4096, 1, "split", err
            )
            assert len(tfs.incidents()) == 1

    def test_serving_504_capture(self, tmp_path):
        x = dsl.placeholder(
            tfs.ScalarType.float32,
            shape=tfs.Shape((None,)),
            name="x",
        )
        fetch = (
            (x * dsl.constant(np.float32(2.0)))
            + dsl.constant(np.float32(1.0))
        ).named("score")
        tfs.serving.register("bb_score", fetch, {"x": "float32"}, warm=False)
        handle = tfs.serving.serve(port=0)
        try:
            with config.override(incident_dir=str(tmp_path)):
                body = tfs.io.frame_to_ipc_bytes(
                    TensorFrame.from_dict(
                        {"x": np.ones(8, dtype=np.float32)}
                    )
                )
                req = urllib.request.Request(
                    handle.url + "/bb_score",
                    data=body,
                    headers={"X-TFS-Timeout-S": "0.000001"},
                    method="POST",
                )
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=10)
                assert ei.value.code == 504
                deadline = time.monotonic() + 5.0
                while not tfs.incidents() and time.monotonic() < deadline:
                    time.sleep(0.02)
                rows = tfs.incidents()
                assert rows
                bundle = tfs.incidents(rows[0]["id"])
                assert bundle["extra"]["status"] == 504
                assert bundle["extra"]["endpoint"] == "bb_score"
        finally:
            telemetry_http.shutdown()
            tfs.serving.reset()

    def test_cross_layer_dedup_stamps_one_id(self, tmp_path):
        e = dl.DeadlineExceeded("x", verb="map_blocks", budget_s=0.1)
        with config.override(incident_dir=str(tmp_path)):
            first = blackbox.capture("deadline", e)
            again = blackbox.capture("serving", e)
            assert first == again
            assert len(tfs.incidents()) == 1
        assert blackbox.state()["captured"] == 1


# ---------------------------------------------------------------------------
# store management + surfaces
# ---------------------------------------------------------------------------


class TestStoreAndSurfaces:
    def test_lru_prune_keeps_newest(self, tmp_path):
        with config.override(
            incident_dir=str(tmp_path),
            incident_max_bundles=2,
            incident_rate_limit_s=0.0,
        ):
            ids = []
            for i in range(4):
                iid = blackbox.capture(f"trig{i}")
                assert iid is not None
                ids.append(iid)
                time.sleep(0.02)  # distinct mtimes for LRU order
            rows = tfs.incidents()
            assert len(rows) == 2
            assert {r["id"] for r in rows} == set(ids[-2:])
        st = blackbox.state()
        assert st["bundles"] == 2
        assert st["bytes"] > 0

    def test_http_routes(self, tmp_path):
        srv = telemetry_http.serve(port=0)
        try:
            with config.override(incident_dir=str(tmp_path)):
                iid = blackbox.capture("deadline")
                code, body = _get(srv.url, "/incidents")
                assert code == 200
                payload = json.loads(body)
                assert payload["recorder"]["captured"] == 1
                assert payload["incidents"][0]["id"] == iid
                code, body = _get(srv.url, f"/incidents/{iid}")
                assert code == 200
                assert json.loads(body)["id"] == iid
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _get(srv.url, "/incidents/inc-nope")
                assert ei.value.code == 404
        finally:
            telemetry_http.shutdown()

    def test_diagnostics_section(self, tmp_path):
        with config.override(incident_dir=str(tmp_path)):
            blackbox.capture("deadline")
            data = tfs.diagnostics(format="json")
            assert data["blackbox"]["captured"] == 1
            assert data["blackbox"]["bundles"] == 1
            text = tfs.diagnostics()
            assert "flight recorder" in text
            assert "1 incident(s) captured" in text

    def test_reset_state_forgets_everything(self, tmp_path):
        with config.override(incident_dir=str(tmp_path)):
            blackbox.capture("deadline")
        blackbox.reset_state()
        st = blackbox.state()
        assert st["captured"] == 0 and st["dedup"] == {}
        # an operator-configured dir is an artifact: files survive reset
        assert len(os.listdir(tmp_path)) == 1

    def test_process_private_dir_reaped_on_reset(self):
        with config.override(incident_rate_limit_s=0.0):
            blackbox.capture("deadline")
        d = blackbox.state()["dir"]
        assert d and os.path.isdir(d)
        blackbox.reset_state()
        assert not os.path.exists(d)

    def test_capture_latency_bounded(self, tmp_path):
        df = _frame(n=512, blocks=8)
        _chain(df)  # populate the span ring + ledgers
        with config.override(incident_dir=str(tmp_path)):
            t0 = time.perf_counter()
            assert blackbox.capture("deadline") is not None
            dt = time.perf_counter() - t0
        # well under one backoff quantum — capture cannot meaningfully
        # extend a fault path that must stay inside its overshoot bound
        assert dt < config.get().retry_backoff_max_s


# ---------------------------------------------------------------------------
# telemetry satellites
# ---------------------------------------------------------------------------


class TestTelemetrySatellites:
    def test_chrome_trace_write_is_atomic(self, tmp_path):
        df = _frame()
        tfs.map_blocks(_double(df), df)
        path = str(tmp_path / "trace.json")
        telemetry.export_chrome_trace(path)
        errors = []
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                try:
                    with open(path) as f:
                        json.loads(f.read())["traceEvents"]
                except Exception as e:  # pragma: no cover - the assert
                    errors.append(repr(e))
                    return

        t = threading.Thread(target=reader)
        t.start()
        try:
            for _ in range(30):
                telemetry.export_chrome_trace(path)
        finally:
            stop.set()
            t.join(timeout=10)
        assert not errors, errors
        # no temp-file residue from the atomic commit
        assert os.listdir(tmp_path) == ["trace.json"]

    def test_spans_dropped_gauge_always_live(self):
        code_text = telemetry.export_prometheus()
        assert "# HELP tfs_spans_dropped " in code_text
        assert "tfs_spans_dropped 0" in code_text
        telemetry.reset()  # registered gauges survive reset
        assert "tfs_spans_dropped" in telemetry.export_prometheus()

    def test_incident_metrics_registered(self, tmp_path):
        with config.override(incident_dir=str(tmp_path)):
            blackbox.capture("deadline")
        text = telemetry.export_prometheus()
        assert "# HELP tfs_incidents_captured " in text
        assert 'tfs_incidents_captured{trigger="deadline"} 1' in text
        assert "# HELP tfs_incident_bytes " in text
        assert "# HELP tfs_incident_capture_seconds " in text
        assert "tfs_incident_capture_seconds_count 1" in text
