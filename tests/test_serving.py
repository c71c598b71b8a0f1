"""Serving runtime: endpoint registry, cross-request micro-batching,
SLO-bounded HTTP dispatch (ISSUE 10).

The acceptance contracts under test:

- Arrow IPC byte helpers round-trip dtypes, cell shapes and block
  structure exactly (server and client share them).
- `register` validates programs against the declared schema at
  registration, proves batchability with the shared row-local walk,
  warm-compiles the bucket ladder — and steady-state traffic compiles
  NOTHING (`jit_shape_compiles` flat across varied request sizes).
- The micro-batcher coalesces concurrent requests into fewer dispatches
  with per-request results bit-identical to direct verb calls; a full
  lane sheds typed `OverloadError`; a deadline-expired request returns
  within its budget without poisoning batch-mates.
- The HTTP front-end maps typed errors to 429 (+Retry-After) / 504 /
  404 / 400, stamps ``request=`` on verb spans (no orphan spans under
  8 concurrent clients), shares the one process server with the
  telemetry routes, and `tfs.telemetry.shutdown()` actually frees the
  port (the PR 8 "no stop" gap).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import config, dsl
from tensorframes_tpu.frame import TensorFrame
from tensorframes_tpu.io import frame_from_ipc_bytes, frame_to_ipc_bytes
from tensorframes_tpu.runtime.executor import default_executor
from tensorframes_tpu.schema import ScalarType, Shape
from tensorframes_tpu.serving import batcher as serve_batcher
from tensorframes_tpu.utils import telemetry, telemetry_http


def _score_fetch(name="score"):
    """Elementwise (row-local => batchable) scoring graph: 2x + 1."""
    x = dsl.placeholder(ScalarType.float32, shape=Shape((None,)), name="x")
    two = dsl.constant(np.float32(2.0))
    one = dsl.constant(np.float32(1.0))
    return ((x * two) + one).named(name)


def _register_score(name="score", **kw):
    return tfs.serving.register(name, _score_fetch(), {"x": "float32"}, **kw)


def _req(n, seed=0):
    rng = np.random.RandomState(seed)
    return TensorFrame.from_dict({"x": rng.rand(n).astype(np.float32)})


# ---------------------------------------------------------------------------
# Arrow IPC byte helpers
# ---------------------------------------------------------------------------


class TestIpcBytes:
    @pytest.mark.parametrize(
        "dtype",
        ["float32", "float64", "int32", "int64", "uint8", "bool"],
    )
    def test_dtype_fidelity(self, dtype):
        data = np.arange(7).astype(dtype)
        df = TensorFrame.from_dict({"v": data})
        out = frame_from_ipc_bytes(frame_to_ipc_bytes(df))
        assert out.info["v"].dtype is ScalarType.from_np_dtype(
            np.dtype(dtype)
        )
        assert np.array_equal(out.column("v").host_values(), data)

    def test_block_structure_survives(self):
        df = TensorFrame.from_dict(
            {"x": np.arange(10, dtype=np.float32)}, num_blocks=3
        )
        out = frame_from_ipc_bytes(frame_to_ipc_bytes(df))
        assert out.block_sizes() == df.block_sizes()

    def test_vector_cells(self):
        df = TensorFrame.from_dict(
            {"m": np.arange(12, dtype=np.float64).reshape(6, 2)}
        )
        out = frame_from_ipc_bytes(frame_to_ipc_bytes(df))
        assert out.info["m"].cell_shape.dims == (2,)
        assert np.array_equal(
            out.column("m").host_values(), df.column("m").host_values()
        )

    def test_multi_column_bitexact(self):
        rng = np.random.RandomState(3)
        df = TensorFrame.from_dict(
            {
                "a": rng.rand(33).astype(np.float32),
                "b": rng.randint(0, 9, 33).astype(np.int64),
            },
            num_blocks=4,
        )
        out = frame_from_ipc_bytes(frame_to_ipc_bytes(df))
        for c in ("a", "b"):
            assert np.array_equal(
                out.column(c).host_values(), df.column(c).host_values()
            )


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_register_and_describe(self):
        ep = _register_score(warm=False)
        assert ep.batchable
        d = ep.describe()
        assert d["columns"] == {
            "x": {"dtype": "float32", "cell_shape": []}
        }
        assert d["outputs"]["score"]["dtype"] == "float32"
        assert tfs.serving.get("score") is ep
        assert [e["name"] for e in tfs.serving.endpoints()] == ["score"]

    def test_schema_dtype_mismatch_raises(self):
        with pytest.raises(ValueError, match="does not fit the declared"):
            tfs.serving.register(
                "bad", _score_fetch(), {"x": "int32"}, warm=False
            )

    def test_missing_schema_column_raises(self):
        with pytest.raises(ValueError, match="does not fit the declared"):
            tfs.serving.register(
                "bad", _score_fetch(), {"y": "float32"}, warm=False
            )

    def test_duplicate_name_needs_replace(self):
        _register_score(warm=False)
        with pytest.raises(ValueError, match="already registered"):
            _register_score(warm=False)
        ep2 = _register_score(warm=False, replace=True)
        assert tfs.serving.get("score") is ep2

    def test_unregister(self):
        _register_score(warm=False)
        assert tfs.serving.unregister("score")
        assert not tfs.serving.unregister("score")
        with pytest.raises(KeyError):
            tfs.serving.get("score")

    def test_reduce_shaped_program_rejected(self):
        x = dsl.placeholder(
            ScalarType.float32, shape=Shape((None,)), name="x"
        )
        total = dsl.reduce_sum(x, axes=[0]).named("t")
        with pytest.raises(ValueError, match="row-preserving"):
            tfs.serving.register("sum", total, {"x": "float32"}, warm=False)

    def test_lazy_plan_registration(self):
        proto = TensorFrame.from_dict({"x": np.zeros(4, np.float32)})
        lz = tfs.map_blocks(_score_fetch("s1"), proto.lazy())
        lz = tfs.map_blocks(
            (
                dsl.placeholder(
                    ScalarType.float32, shape=Shape((None,)), name="s1"
                )
                * dsl.constant(np.float32(3.0))
            ).named("s2"),
            lz,
        )
        ep = tfs.serving.register("chain", lz, {"x": "float32"}, warm=False)
        assert ep.batchable
        req = _req(6, seed=1)
        out = ep.run_frame(req)
        expect = (req.column("x").host_values() * 2.0 + 1.0) * 3.0
        got = out.column("s2").host_values()
        assert np.array_equal(got, expect.astype(np.float32))

    def test_lazy_plan_rejects_feed_dict(self):
        proto = TensorFrame.from_dict({"x": np.zeros(4, np.float32)})
        lz = tfs.map_blocks(_score_fetch(), proto.lazy())
        with pytest.raises(ValueError, match="feed_dict"):
            tfs.serving.register(
                "chain", lz, {"x": "float32"}, feed_dict={"x": "x"},
                warm=False,
            )

    def test_non_rowlocal_not_batchable(self):
        # matmul against a weight constant is outside the conservative
        # row-local op set: servable, but never coalesced
        x = dsl.placeholder(
            ScalarType.float32, shape=Shape((None, 3)), name="x"
        )
        w = dsl.constant(np.eye(3, dtype=np.float32))
        y = dsl.matmul(x, w).named("y")
        ep = tfs.serving.register(
            "mm", y, {"x": ("float32", (3,))}, warm=False
        )
        assert not ep.batchable
        assert ep.warm() == ()  # warm is a no-op off the row-local path
        out = ep.run_frame(
            TensorFrame.from_dict(
                {"x": np.arange(6, dtype=np.float32).reshape(2, 3)}
            )
        )
        assert out.column("y").host_values().shape == (2, 3)

    def test_request_validation(self):
        ep = _register_score(warm=False)
        with pytest.raises(ValueError, match="missing column"):
            ep.validate_request(
                TensorFrame.from_dict({"y": np.zeros(2, np.float32)})
            )
        with pytest.raises(ValueError, match="dtype"):
            ep.validate_request(
                TensorFrame.from_dict({"x": np.zeros(2, np.float64)})
            )

    def test_warm_compiles_ladder_then_zero_steady_state(self):
        from tensorframes_tpu import shape_policy as sp

        ex = default_executor()
        ep = _register_score(max_batch_rows=64)  # warm=config default: on
        assert list(ep.warmed_rungs) == sp.bucket_ladder(64)
        base = ex.jit_shape_compiles()
        # varied request sizes below the max batch all land on warmed
        # rungs: ZERO new compiles at steady state
        for n in (1, 3, 5, 8, 13, 21, 34, 55, 64):
            ep_out = ep.run_frame(_req(n, seed=n))
            assert ep_out.nrows == n
            fut = serve_batcher().submit(ep, _req(n, seed=n + 100))
            fut.result(timeout=30)
        assert ex.jit_shape_compiles() == base


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------


class TestBatcher:
    def test_concurrent_submits_coalesce_bit_identical(self):
        ep = _register_score(warm=False)
        reqs = [_req(3, seed=i) for i in range(8)]
        expected = [
            (r.column("x").host_values() * 2.0 + 1.0).astype(np.float32)
            for r in reqs
        ]
        results = [None] * 8
        barrier = threading.Barrier(8)

        def client(i):
            barrier.wait(timeout=30)
            fut = serve_batcher().submit(ep, reqs[i], request_id=f"r{i}")
            results[i] = np.asarray(
                fut.result(timeout=30).column("score").host_values()
            )

        ts = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
        for i in range(8):
            assert np.array_equal(results[i], expected[i]), i
        snap = serve_batcher().snapshot()
        # coalescing happened: fewer dispatches than requests
        assert snap["batches"] < snap["batched_requests"] == 8

    def test_rung_fill_closes_early(self):
        # 8 rows == the smallest ladder rung: the batch must dispatch
        # WITHOUT waiting out a long window
        ep = _register_score(warm=False)
        with config.override(serve_batch_window_ms=10_000.0):
            t0 = time.perf_counter()
            fut = serve_batcher().submit(ep, _req(8))
            fut.result(timeout=30)
            assert time.perf_counter() - t0 < 5.0

    def test_window_zero_disables_coalescing(self):
        ep = _register_score(warm=False)
        with config.override(serve_batch_window_ms=0.0):
            fut = serve_batcher().submit(ep, _req(4))
            fut.result(timeout=30)
        assert serve_batcher().snapshot()["inline"] == 1

    def test_queue_limit_sheds_typed(self):
        ep = _register_score(warm=False)

        # hold the lane's dispatcher inside a hung dispatch, then
        # overflow the queue behind it
        from tensorframes_tpu.testing import faults as chaos

        with config.override(
            serve_queue_limit=1, serve_batch_window_ms=5.0
        ):
            with chaos.inject(
                rate=1.0, seed=1, fault="hang", delay_s=2.0, max_faults=1
            ):
                with tfs.deadline_scope(timeout_s=20.0):
                    first = serve_batcher().submit(ep, _req(2))
                time.sleep(0.5)  # dispatcher is inside the hang now
                with tfs.deadline_scope(timeout_s=20.0):
                    serve_batcher().submit(ep, _req(2))  # fills queue
                    with pytest.raises(tfs.OverloadError) as ei:
                        serve_batcher().submit(ep, _req(2))
                assert ei.value.retry_after_s > 0
                assert ei.value.limit == 1
                first.result(timeout=30)

    def test_bad_request_fails_alone(self):
        ep = _register_score(warm=False)
        with pytest.raises(ValueError, match="missing column"):
            serve_batcher().submit(
                ep, TensorFrame.from_dict({"nope": np.zeros(2, np.int32)})
            )
        # the lane still serves good requests
        fut = serve_batcher().submit(ep, _req(2))
        assert fut.result(timeout=30).nrows == 2

    def test_multi_block_request_coalesces_to_one_dispatch(self):
        ep = _register_score(warm=False)
        req = TensorFrame.from_dict(
            {"x": np.arange(9, dtype=np.float32)}, num_blocks=3
        )
        fut = serve_batcher().submit(ep, req)
        out = fut.result(timeout=30)
        assert np.array_equal(
            out.column("score").host_values(),
            (np.arange(9) * 2.0 + 1.0).astype(np.float32),
        )


# ---------------------------------------------------------------------------
# HTTP front-end
# ---------------------------------------------------------------------------


@pytest.fixture
def served():
    handle = tfs.serving.serve(port=0)
    client = tfs.serving.ServingClient(handle.url)
    yield handle, client
    handle.close()
    telemetry.shutdown()


class TestServer:
    def test_round_trip_and_echo(self, served):
        _handle, client = served
        _register_score(warm=False)
        req = _req(5)
        out = client.run("score", req, timeout_s=10.0, request_id="rt-1")
        assert np.array_equal(
            out.column("score").host_values(),
            (req.column("x").host_values() * 2.0 + 1.0).astype(np.float32),
        )

    def test_unknown_endpoint_404(self, served):
        _handle, client = served
        with pytest.raises(tfs.serving.ServingError) as ei:
            client.run("ghost", _req(2), timeout_s=5.0)
        assert ei.value.status == 404

    def test_malformed_body_400(self, served):
        handle, _client = served
        r = urllib.request.Request(
            f"{handle.url}/anything", data=b"not arrow", method="POST"
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(r, timeout=10)
        assert ei.value.code in (400, 404)

    def test_schema_violation_400(self, served):
        _handle, client = served
        _register_score(warm=False)
        with pytest.raises(tfs.serving.ServingError) as ei:
            client.run(
                "score", {"x": np.zeros(3, np.float64)}, timeout_s=5.0
            )
        assert ei.value.status == 400

    def test_deadline_504_within_budget(self, served):
        _handle, client = served
        _register_score(warm=False)
        from tensorframes_tpu.testing import faults as chaos

        t0 = time.perf_counter()
        with chaos.inject(rate=1.0, seed=1, fault="hang", delay_s=30.0):
            with pytest.raises(tfs.DeadlineExceeded):
                client.run("score", _req(3), timeout_s=0.3)
        # one backoff quantum of slack over the 0.3s budget
        assert time.perf_counter() - t0 < 3.0
        # the lane drained; a clean call works and is bit-identical
        req = _req(3, seed=9)
        out = client.run("score", req, timeout_s=10.0)
        assert np.array_equal(
            out.column("score").host_values(),
            (req.column("x").host_values() * 2.0 + 1.0).astype(np.float32),
        )

    def test_overload_429_with_retry_after(self, served):
        handle, client = served
        _register_score(warm=False)
        from tensorframes_tpu.testing import faults as chaos

        sheds = []
        with config.override(serve_queue_limit=1):
            with chaos.inject(
                rate=1.0, seed=1, fault="hang", delay_s=1.5, max_faults=1
            ):
                hold = threading.Thread(
                    target=lambda: client.run(
                        "score", _req(2), timeout_s=15.0
                    )
                )
                hold.start()
                time.sleep(0.5)  # dispatcher inside the hang

                def burst():
                    try:
                        client.run("score", _req(2), timeout_s=15.0)
                    except tfs.OverloadError as e:
                        sheds.append(e)

                ts = [
                    threading.Thread(target=burst) for _ in range(4)
                ]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=60)
                hold.join(timeout=60)
        assert sheds, "burst beyond queue limit 1 shed nothing"
        assert all(e.retry_after_s > 0 for e in sheds)
        # the raw HTTP response carries a whole-second Retry-After
        # header (checked end-to-end through urllib, not our client)
        with config.override(serve_queue_limit=1):
            with chaos.inject(
                rate=1.0, seed=2, fault="hang", delay_s=1.5, max_faults=1
            ):
                hold = threading.Thread(
                    target=lambda: client.run(
                        "score", _req(2), timeout_s=15.0
                    )
                )
                hold.start()
                time.sleep(0.5)
                body = frame_to_ipc_bytes(_req(2))
                filler = threading.Thread(
                    target=lambda: _swallow(
                        lambda: client.run("score", _req(2), timeout_s=15.0)
                    )
                )
                filler.start()
                time.sleep(0.1)
                r = urllib.request.Request(
                    f"{handle.url}/score", data=body, method="POST",
                    headers={"X-TFS-Timeout-S": "15"},
                )
                try:
                    urllib.request.urlopen(r, timeout=10)
                    shed_header = None
                except urllib.error.HTTPError as e:
                    assert e.code == 429
                    shed_header = e.headers.get("Retry-After")
                    payload = json.loads(e.read().decode())
                    assert payload["error"] == "OverloadError"
                filler.join(timeout=60)
                hold.join(timeout=60)
        if shed_header is not None:
            assert int(shed_header) >= 1

    def test_overload_admitted_requests_finish_bit_identical(self, served):
        """A burst behind a wedged dispatch and a 1-deep lane queue:
        every client returns, the excess sheds typed with a positive
        hint, and what was admitted equals the unbatched answer."""
        _handle, client = served
        _register_score(warm=False)
        from tensorframes_tpu.testing import faults as chaos

        req = _req(5, seed=4)
        want = (req.column("x").host_values() * 2.0 + 1.0).astype(
            np.float32
        )
        sheds, oks = [], []

        def one():
            try:
                out = client.run("score", req, timeout_s=15.0)
                oks.append(np.asarray(out.column("score").host_values()))
            except tfs.OverloadError as e:
                sheds.append(e)

        with config.override(serve_queue_limit=1):
            with chaos.inject(
                rate=1.0, seed=1, fault="hang", delay_s=1.5, max_faults=1
            ):
                hold = threading.Thread(target=one)
                hold.start()
                time.sleep(0.5)  # dispatcher inside the hang
                ts = [threading.Thread(target=one) for _ in range(4)]
                for t in ts:
                    t.start()
                for t in ts + [hold]:
                    t.join(timeout=60)
        assert not any(t.is_alive() for t in ts + [hold])
        assert len(sheds) + len(oks) == 5
        assert sheds and all(e.retry_after_s > 0 for e in sheds)
        assert oks and all(np.array_equal(o, want) for o in oks)

    def test_shared_server_still_serves_telemetry(self, served):
        handle, _client = served
        base = f"http://{handle.host}:{handle.port}"
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            assert r.status == 200
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            assert b"tfs_" in r.read()
        with urllib.request.urlopen(base, timeout=10) as r:
            assert "/serve" in json.loads(r.read().decode())["routes"]

    def test_concurrent_clients_labeled_spans_no_orphans(self, served):
        _handle, client = served
        _register_score(warm=False)
        errors = []

        def one(i):
            try:
                req = _req(3, seed=i)
                out = client.run(
                    "score", req, timeout_s=15.0, request_id=f"cc-{i}"
                )
                expect = (
                    req.column("x").host_values() * 2.0 + 1.0
                ).astype(np.float32)
                assert np.array_equal(
                    out.column("score").host_values(), expect
                )
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append((i, repr(e)))

        ts = [threading.Thread(target=one, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not errors, errors
        spans = telemetry.spans()
        labeled = [
            s for s in spans
            if s.kind == "verb" and "request" in s.attrs
        ]
        assert labeled, "no verb span carries a request= label"
        seen = ",".join(str(s.attrs["request"]) for s in labeled)
        for i in range(8):
            assert f"cc-{i}" in seen, f"request cc-{i} unattributed"
        # no orphan parents: every parent id resolves inside the export
        trace = telemetry.export_chrome_trace()
        ids = {
            ev["args"]["span_id"]
            for ev in trace["traceEvents"]
            if "span_id" in ev.get("args", {})
        }
        for ev in trace["traceEvents"]:
            parent = ev.get("args", {}).get("parent_id")
            if parent is not None:
                assert parent in ids, f"orphan parent {parent}"

    def test_shutdown_frees_port_and_remount(self):
        handle = tfs.serving.serve(port=0)
        _register_score(warm=False)
        port = handle.port
        client = tfs.serving.ServingClient(handle.url)
        client.run("score", _req(2), timeout_s=10.0)
        assert telemetry.shutdown() is True
        assert telemetry.shutdown() is False  # idempotent no-op
        assert telemetry_http.active_server() is None
        with pytest.raises(Exception):
            client.run("score", _req(2), timeout_s=2.0)
        # mounts survive shutdown: a fresh serve() re-binds and serves
        handle2 = tfs.serving.serve(port=0)
        client2 = tfs.serving.ServingClient(handle2.url)
        out = client2.run("score", _req(2), timeout_s=10.0)
        assert out.nrows == 2
        handle2.close()
        telemetry.shutdown()
        assert port  # silence lint

    def test_close_unmounts_but_keeps_server(self, served):
        handle, client = served
        _register_score(warm=False)
        handle.close()
        with pytest.raises(tfs.serving.ServingError) as ei:
            client.run("score", _req(2), timeout_s=5.0)
        assert ei.value.status == 404
        base = f"http://{handle.host}:{handle.port}"
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            assert r.status == 200


def _swallow(fn):
    try:
        return fn()
    except Exception:
        return None


# ---------------------------------------------------------------------------
# hygiene
# ---------------------------------------------------------------------------


class TestHygiene:
    def test_reset_stops_lane_threads(self):
        ep = _register_score(warm=False)
        fut = serve_batcher().submit(ep, _req(2))
        fut.result(timeout=30)
        assert any(
            t.name.startswith("tfs-serve-") for t in threading.enumerate()
        )
        tfs.serving.reset()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if not any(
                t.name.startswith("tfs-serve-")
                for t in threading.enumerate()
            ):
                break
            time.sleep(0.05)
        assert not any(
            t.name.startswith("tfs-serve-") for t in threading.enumerate()
        ), "batching lane thread leaked past serving.reset()"

    def test_pending_gauge_registered(self):
        _register_score(warm=False)
        text = telemetry.export_prometheus()
        assert "tfs_serve_pending" in text

    def test_reset_clears_active_handle(self):
        handle = tfs.serving.serve(port=0)
        assert tfs.serving.active() is handle
        tfs.serving.reset()
        assert tfs.serving.active() is None
        telemetry.shutdown()

    def test_duplicate_register_rejected_before_warm(self):
        # the cheap name check runs BEFORE probe/warm compiles: a
        # rejected duplicate must not have paid (or counted) any warm
        # rung compiles
        def warm_count():
            return sum(
                v
                for k, v in telemetry.flat_counters().items()
                if k.startswith("serve_warm_rungs")
            )

        _register_score(warm=False)
        before = warm_count()
        with pytest.raises(ValueError, match="already registered"):
            _register_score(warm=True, max_batch_rows=4096)
        assert warm_count() == before

    def test_submit_after_drop_gets_fresh_lane(self):
        ep = _register_score(warm=False)
        fut = serve_batcher().submit(ep, _req(2))
        fut.result(timeout=30)
        # drop the lane, then submit again: a fresh lane must serve it
        serve_batcher().drop(ep.name)
        fut2 = serve_batcher().submit(ep, _req(3))
        assert fut2.result(timeout=30).nrows == 3
