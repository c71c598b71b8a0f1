"""Deadline propagation, cooperative cancellation, admission control.

The ISSUE 9 acceptance surface:

- a chained lazy map→reduce with an injected hang exceeds its
  ``timeout_s`` by less than one backoff quantum, raises the typed
  `DeadlineExceeded`, leaves no live pipeline threads / open fds, and
  the next verb on the same executor runs clean (no poisoned cache, no
  stuck admission slot);
- under overload the admission controller SHEDS with `OverloadError`
  (queue depth + retry-after hint) while every admitted verb returns
  bit-identical results;
- backoff sleeps clip to the remaining deadline (a timed-out verb
  never sleeps past its budget);
- ingest deadline expiry tears the stage graph down with the
  consumer-abandon guarantees (threads exit, fds close).
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import config, dsl
from tensorframes_tpu import io as tio
from tensorframes_tpu.frame import TensorFrame
from tensorframes_tpu.runtime import deadline as dl
from tensorframes_tpu.runtime import faults as rtf
from tensorframes_tpu.testing import faults as chaos
from tensorframes_tpu.utils import telemetry
from tensorframes_tpu.utils.inspection import executor_stats


def _frame(n=64, blocks=4, seed=0):
    rng = np.random.RandomState(seed)
    return TensorFrame.from_dict(
        {"x": rng.rand(n).astype(np.float32)}, num_blocks=blocks
    )


def _double(df):
    return (tfs.block(df, "x") * 2.0 + 1.0).named("y")


def _sum_fetch(df, col="x"):
    return dsl.reduce_sum(
        tfs.block(df, col, tf_name=f"{col}_input"), axes=[0]
    ).named(col)


def _fd_count():
    return len(os.listdir("/proc/self/fd"))


def _ingest_threads():
    return [
        t.name
        for t in threading.enumerate()
        if t.is_alive() and t.name.startswith("tfs-ingest")
    ]


def _wait_ingest_threads_gone(timeout=5.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if not _ingest_threads():
            return True
        time.sleep(0.05)
    return not _ingest_threads()


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


class TestPrimitives:
    def test_deadline_after_remaining_expired(self):
        d = dl.Deadline.after(0.05)
        assert 0.0 < d.remaining() <= 0.05
        assert not d.expired()
        time.sleep(0.07)
        assert d.expired()
        assert d.remaining() < 0.0

    def test_tightened_min_wins(self):
        a = dl.Deadline.after(10.0)
        b = dl.Deadline.after(0.1)
        assert a.tightened(b) is b
        assert b.tightened(a) is b
        assert a.tightened(None) is a

    def test_unbounded_scope_check_is_noop(self):
        s = dl.CancelScope()
        s.check("x")  # no deadline, not cancelled: nothing raises
        assert s.remaining() is None
        assert not s.should_abort()

    def test_cancel_raises_and_wakes_sleep(self):
        s = dl.CancelScope(verb="t")
        t0 = time.monotonic()
        done = []

        def sleeper():
            try:
                s.sleep(10.0, "test")
            except dl.Cancelled as e:
                done.append(e)

        th = threading.Thread(target=sleeper)
        th.start()
        time.sleep(0.1)
        s.cancel("user abort")
        th.join(timeout=5.0)
        assert not th.is_alive()
        assert time.monotonic() - t0 < 5.0
        assert done and done[0].reason == "user abort"
        with pytest.raises(dl.Cancelled):
            s.check("after")

    def test_sleep_clips_to_deadline(self):
        s = dl.CancelScope(deadline=dl.Deadline.after(0.15), verb="t")
        t0 = time.monotonic()
        with pytest.raises(dl.DeadlineExceeded) as ei:
            s.sleep(10.0, "test")
        elapsed = time.monotonic() - t0
        assert elapsed < 2.0  # woke at the deadline, not after 10s
        assert ei.value.verb == "t"
        assert ei.value.budget_s == pytest.approx(0.15, abs=0.05)

    def test_module_level_check_without_scope(self):
        assert dl.current_scope() is None
        dl.check("free")  # no ambient scope: no-op
        assert dl.remaining() is None

    def test_nested_scope_tightens_never_loosens(self):
        with dl.verb_scope("outer", timeout_s=5.0) as outer:
            with dl.verb_scope("inner", timeout_s=0.05) as inner:
                assert inner.remaining() <= 0.05 + 1e-6
            # an inner timeout LARGER than the outer budget cannot
            # extend it: the inherited (tighter) deadline wins. Read
            # the OUTER clock first: both scopes share one deadline,
            # so the later (inner) read is necessarily <= the earlier
            # one — reading inner first raced the monotonic clock and
            # flaked by sub-microsecond jitter.
            with dl.verb_scope("inner2", timeout_s=100.0) as inner2:
                outer_rem = outer.remaining()
                assert inner2.remaining() <= outer_rem + 1e-6
            # nested scopes share the cancel event
            with dl.verb_scope("inner3") as inner3:
                outer.cancel("stop")
                assert inner3.cancelled

    def test_typed_errors_classify_deterministic(self):
        assert rtf.classify(dl.DeadlineExceeded("x")) == rtf.DETERMINISTIC
        assert rtf.classify(dl.Cancelled("x")) == rtf.DETERMINISTIC
        assert (
            rtf.classify(dl.OverloadError("x", 1, 1, 0.1))
            == rtf.DETERMINISTIC
        )

    def test_deadline_never_burned_as_retry(self):
        calls = [0]

        def thunk():
            calls[0] += 1
            raise dl.DeadlineExceeded("boom")

        scope = rtf.scope("t", attempts=5)
        with pytest.raises(dl.DeadlineExceeded):
            scope.dispatch(thunk, what="t")
        assert calls[0] == 1  # exactly one attempt, no retry burned


# ---------------------------------------------------------------------------
# interruptible backoff (satellite 1)
# ---------------------------------------------------------------------------


class TestInterruptibleBackoff:
    def test_backoff_clipped_to_deadline(self):
        """A transient retry whose backoff would sleep past the budget
        wakes AT the deadline and raises DeadlineExceeded — the verb
        never sleeps out its full backoff schedule."""
        calls = [0]

        def always_transient():
            calls[0] += 1
            raise RuntimeError("UNAVAILABLE: injected for backoff test")

        t0 = time.monotonic()
        with config.override(
            retry_backoff_base_s=30.0, retry_backoff_max_s=30.0,
            retry_jitter=0.0,
        ):
            with dl.verb_scope("t", timeout_s=0.2):
                scope = rtf.scope("t", attempts=3, budget=10)
                with pytest.raises(dl.DeadlineExceeded):
                    scope.dispatch(always_transient, what="t")
        elapsed = time.monotonic() - t0
        # one failed attempt, then the 30s backoff clipped to ~0.2s
        assert calls[0] == 1
        assert elapsed < 2.0

    def test_backoff_runs_full_without_deadline(self):
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] == 1:
                raise RuntimeError("UNAVAILABLE: once")
            return "ok"

        with config.override(
            retry_backoff_base_s=0.01, retry_backoff_max_s=0.01,
            retry_jitter=0.0,
        ):
            scope = rtf.scope("t", attempts=2, budget=10)
            assert scope.dispatch(flaky, what="t") == "ok"
        assert calls[0] == 2

    def test_explicit_sleep_callable_still_honored(self):
        """Tests inject sleep= to observe the schedule; that seam keeps
        working (no deadline active)."""
        slept = []
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] < 3:
                raise RuntimeError("UNAVAILABLE: twice")
            return 1

        scope = rtf.scope("t", attempts=3, budget=10)
        assert scope.dispatch(flaky, what="t", sleep=slept.append) == 1
        assert len(slept) == 2


# ---------------------------------------------------------------------------
# hang injection (satellite 2)
# ---------------------------------------------------------------------------


class TestHangInjection:
    def test_nth_hang_fires_once_and_proceeds(self):
        df = _frame()
        t0 = time.monotonic()
        with chaos.inject(nth=[1], fault="hang", delay_s=0.15) as plan:
            out = tfs.map_blocks(_double(df), df)
        assert plan.injected == 1
        assert plan.faulted_ordinals == [1]
        assert time.monotonic() - t0 >= 0.15
        np.testing.assert_array_equal(
            np.asarray(out["y"].values),
            np.asarray(df["x"].values) * 2.0 + 1.0,
        )

    def test_rate_hang_deterministic_across_runs(self):
        df = _frame()
        with chaos.inject(rate=0.5, seed=11, fault="hang",
                          delay_s=0.0) as p1:
            tfs.map_blocks(_double(df), df)
        with chaos.inject(rate=0.5, seed=11, fault="hang",
                          delay_s=0.0) as p2:
            tfs.map_blocks(_double(df), df)
        assert p1.faulted_ordinals == p2.faulted_ordinals
        assert p1.dispatches == p2.dispatches

    def test_max_faults_bounds_hangs(self):
        df = _frame()
        with chaos.inject(rate=1.0, seed=0, fault="hang", delay_s=0.0,
                          max_faults=2) as plan:
            tfs.map_blocks(_double(df), df)
        assert plan.injected == 2

    def test_unknown_fault_class_still_rejected(self):
        with pytest.raises(ValueError):
            chaos.FaultPlan(fault="wedge")
        with pytest.raises(ValueError):
            chaos.StageFaultPlan(fault="wedge")


# ---------------------------------------------------------------------------
# verb timeouts end to end
# ---------------------------------------------------------------------------


class TestVerbTimeouts:
    def test_map_blocks_hang_trips_timeout(self):
        df = _frame()
        t0 = time.monotonic()
        with chaos.inject(nth=[0], fault="hang", delay_s=10.0):
            with pytest.raises(dl.DeadlineExceeded) as ei:
                tfs.map_blocks(_double(df), df, timeout_s=0.3)
        elapsed = time.monotonic() - t0
        assert elapsed < 1.3  # promptly, not after the 10s hang
        e = ei.value
        assert e.verb == "map_blocks"
        # partial-work accounting from the block schedule
        assert getattr(e, "tfs_blocks_issued", None) is not None
        assert getattr(e, "tfs_blocks_unissued", None) is not None
        # counters + ledger
        flat = telemetry.flat_counters()
        assert flat.get("deadline_exceeded{verb=map_blocks}", 0) >= 1
        assert executor_stats()["faults"]["deadlines"] >= 1

    def test_acceptance_chained_lazy_hang(self):
        """THE acceptance scenario: chained lazy map→reduce + injected
        hang exceeds timeout_s by less than one backoff quantum,
        raises DeadlineExceeded, leaves no pipeline threads / fds, and
        the next verb on the same executor runs clean."""
        df = _frame(n=128, blocks=4, seed=3)
        fds0 = _fd_count()
        threads0 = set(t.name for t in threading.enumerate())

        def chain(frame, **kw):
            lz = frame.lazy().map_blocks(_double(frame))
            fetch = dsl.reduce_sum(
                tfs.block(lz, "y", tf_name="y_input"), axes=[0]
            ).named("y")
            return tfs.reduce_blocks(fetch, lz, **kw)

        # fault-free reference on the same executor
        ref = float(np.asarray(chain(df)))

        timeout = 0.4
        quantum = config.get().retry_backoff_max_s  # one backoff quantum
        with config.override(max_concurrent_verbs=2):
            t0 = time.monotonic()
            with chaos.inject(nth=[0], fault="hang", delay_s=30.0):
                with pytest.raises(dl.DeadlineExceeded):
                    chain(df, timeout_s=timeout)
            overshoot = (time.monotonic() - t0) - timeout
            assert overshoot < quantum, (
                f"overshoot {overshoot:.3f}s >= backoff quantum "
                f"{quantum:.3f}s"
            )
            # no stuck admission slot: in-flight drained
            assert dl.controller().in_flight_now() == 0
            # no leaked pipeline threads / fds
            assert not _ingest_threads()
            new_threads = (
                set(t.name for t in threading.enumerate()) - threads0
            )
            assert not any(n.startswith("tfs-") for n in new_threads), (
                new_threads
            )
            assert _fd_count() <= fds0 + 2
            # the next verb on the same executor runs clean and
            # bit-identical (no poisoned compile cache)
            again = float(np.asarray(chain(df)))
        assert again == ref

    def test_deadline_storm_leaks_no_thread_and_no_slot(self):
        """Verb after verb wedged by injected hangs, then a stalled
        stream, each killed by a small budget: every one raises, the
        process is left with no thread it did not have before and a
        drained admission gate, and the next call is bit-identical."""
        df = _frame(n=256, blocks=4, seed=5)
        fetch = _sum_fetch(df)
        ref = float(np.asarray(tfs.reduce_blocks(fetch, df)))
        before = {t.ident for t in threading.enumerate() if t.is_alive()}
        storm = 3
        hits = 0
        with chaos.inject(rate=1.0, seed=1, fault="hang", delay_s=30.0):
            for _ in range(storm):
                try:
                    tfs.reduce_blocks(fetch, df, timeout_s=0.05)
                except dl.DeadlineExceeded:
                    hits += 1

        def stalling_chunks():
            for i in range(10_000):
                time.sleep(0.02)
                yield TensorFrame.from_dict(
                    {"x": np.ones(16, dtype=np.float32) * i}
                )

        try:
            tfs.reduce_blocks_stream(fetch, stalling_chunks(), timeout_s=0.2)
        except dl.DeadlineExceeded:
            hits += 1
        assert hits == storm + 1
        # teardown is cooperative: wait for it, within a bound
        end = time.monotonic() + 10.0
        leaked = None
        while time.monotonic() < end:
            leaked = [
                t.name for t in threading.enumerate()
                if t.is_alive() and t.ident not in before
            ]
            if not leaked:
                break
            time.sleep(0.05)
        assert not leaked, leaked
        assert dl.controller().in_flight_now() == 0
        assert float(np.asarray(tfs.reduce_blocks(fetch, df))) == ref

    def test_default_verb_timeout_config_knob(self):
        df = _frame()
        with config.override(default_verb_timeout_s=0.2):
            with chaos.inject(nth=[0], fault="hang", delay_s=10.0):
                t0 = time.monotonic()
                with pytest.raises(dl.DeadlineExceeded):
                    tfs.map_blocks(_double(df), df)
                assert time.monotonic() - t0 < 2.0

    def test_generous_timeout_bit_identical(self):
        df = _frame(seed=5)
        ref = np.asarray(tfs.map_blocks(_double(df), df)["y"].values)
        out = np.asarray(
            tfs.map_blocks(_double(df), df, timeout_s=60.0)["y"].values
        )
        np.testing.assert_array_equal(ref, out)

    def test_reduce_and_aggregate_accept_timeout(self):
        df = _frame(seed=6)
        r = tfs.reduce_blocks(_sum_fetch(df), df, timeout_s=60.0)
        assert np.isfinite(float(np.asarray(r)))
        kf = TensorFrame.from_dict(
            {
                "k": np.array([0, 0, 1, 1], dtype=np.int64),
                "x": np.ones(4, dtype=np.float32),
            }
        )
        out = tfs.aggregate(
            _sum_fetch(kf), tfs.group_by(kf, "k"), timeout_s=60.0
        )
        assert out.nrows == 2

    def test_deadline_scope_shared_budget(self):
        """A chain under tfs.deadline_scope shares ONE budget end to
        end — the second verb inherits what the first left."""
        df = _frame()
        with chaos.inject(nth=[0], fault="hang", delay_s=10.0):
            with pytest.raises(dl.DeadlineExceeded):
                with tfs.deadline_scope(timeout_s=0.25):
                    m = tfs.map_blocks(_double(df), df)  # hangs here
                    tfs.reduce_blocks(_sum_fetch(df, "y"), m)

    def test_scope_cancel_aborts_verb(self):
        df = _frame()
        errs = []

        def run(scope_holder):
            with tfs.deadline_scope() as sc:
                scope_holder.append(sc)
                try:
                    with chaos.inject(rate=1.0, fault="hang",
                                      delay_s=10.0):
                        tfs.map_blocks(_double(df), df)
                except dl.Cancelled as e:
                    errs.append(e)

        holder = []
        th = threading.Thread(target=run, args=(holder,))
        th.start()
        time.sleep(0.2)
        assert holder
        holder[0].cancel("test abort")
        th.join(timeout=10.0)
        assert not th.is_alive()
        assert errs, "verb did not observe the cancel"


# ---------------------------------------------------------------------------
# deadline mid-stream: ingest teardown guarantees (satellite 3)
# ---------------------------------------------------------------------------


class TestDeadlineMidStream:
    def test_stream_source_stall_trips_deadline(self):
        def frames():
            for i in range(1000):
                time.sleep(0.05)
                yield TensorFrame.from_dict(
                    {"x": np.ones(8, dtype=np.float32) * i}
                )

        df = _frame()
        t0 = time.monotonic()
        with pytest.raises(dl.DeadlineExceeded):
            tfs.reduce_blocks_stream(
                _sum_fetch(df), frames(), timeout_s=0.3
            )
        assert time.monotonic() - t0 < 2.0
        assert _wait_ingest_threads_gone()

    def test_deadline_mid_stream_threads_exit_fds_close(self, tmp_path):
        pytest.importorskip("pyarrow")
        rng = np.random.RandomState(0)
        for i in range(4):
            df = TensorFrame.from_dict(
                {"x": rng.rand(64).astype(np.float32)}, num_blocks=2
            )
            tio.write_parquet(
                df, str(tmp_path / f"shard-{i:03d}.parquet")
            )
        fds0 = _fd_count()
        probe = _frame()
        t0 = time.monotonic()
        with chaos.inject_stage(
            stage="decode", rate=1.0, fault="hang", delay_s=10.0
        ):
            with pytest.raises(dl.DeadlineExceeded):
                tfs.reduce_blocks_stream(
                    _sum_fetch(probe),
                    tfs.stream_dataset(str(tmp_path)),
                    timeout_s=0.3,
                )
        assert time.monotonic() - t0 < 3.0
        # the deadline path gives the ABANDON guarantees: every
        # pipeline thread exits (the hang wakes on the cancel event)
        # and the shard file handles close
        assert _wait_ingest_threads_gone(timeout=8.0), _ingest_threads()
        time.sleep(0.1)
        assert _fd_count() <= fds0 + 2
        # and the stream path works again afterwards
        total = tfs.reduce_blocks_stream(
            _sum_fetch(probe), tfs.stream_dataset(str(tmp_path))
        )
        assert np.isfinite(float(np.asarray(total)))


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_unlimited_by_default(self):
        df = _frame()
        snap = dl.controller().snapshot()
        assert snap["limit"] == 0
        tfs.map_blocks(_double(df), df)  # no gate engaged
        assert dl.controller().in_flight_now() == 0

    def test_shed_with_zero_queue(self):
        df = _frame()
        release = dl.controller().admit("holder", None)
        shed0 = dl.controller().snapshot()["shed"]
        try:
            with config.override(
                max_concurrent_verbs=1, admission_queue_limit=0
            ):
                with pytest.raises(tfs.OverloadError) as ei:
                    tfs.map_blocks(_double(df), df)
        finally:
            release()
        e = ei.value
        assert e.limit == 1
        assert e.queue_depth == 0
        assert e.retry_after_s > 0.0
        snap = dl.controller().snapshot()
        assert snap["shed"] == shed0 + 1
        assert telemetry.flat_counters().get("verbs_shed", 0) >= 1
        assert executor_stats()["admission"]["shed"] >= 1
        assert executor_stats()["faults"]["shed"] >= 1
        # the slot is free again: verb runs clean
        out = tfs.map_blocks(_double(df), df)
        assert out.nrows == df.nrows

    def test_queue_then_admitted(self):
        df = _frame()
        release = dl.controller().admit("holder", None)
        got = []
        with config.override(
            max_concurrent_verbs=1, admission_queue_limit=4,
            admission_wait_timeout_s=30.0,
        ):
            th = threading.Thread(
                target=lambda: got.append(
                    np.asarray(tfs.map_blocks(_double(df), df)["y"].values)
                )
            )
            th.start()
            deadline = time.monotonic() + 5.0
            while (
                dl.controller().queue_depth() == 0
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert dl.controller().queue_depth() == 1
            release()
            th.join(timeout=30.0)
        assert not th.is_alive()
        assert got
        np.testing.assert_array_equal(
            got[0], np.asarray(df["x"].values) * 2.0 + 1.0
        )
        assert (
            telemetry.flat_counters().get("admission_wait_seconds", 0.0)
            > 0.0
        )

    def test_wait_timeout_sheds(self):
        df = _frame()
        release = dl.controller().admit("holder", None)
        try:
            with config.override(
                max_concurrent_verbs=1, admission_queue_limit=4,
                admission_wait_timeout_s=0.15,
            ):
                t0 = time.monotonic()
                with pytest.raises(tfs.OverloadError):
                    tfs.map_blocks(_double(df), df)
                assert 0.1 < time.monotonic() - t0 < 5.0
        finally:
            release()
        assert dl.controller().queue_depth() == 0

    def test_deadline_while_queued(self):
        df = _frame()
        release = dl.controller().admit("holder", None)
        try:
            with config.override(
                max_concurrent_verbs=1, admission_queue_limit=4,
                admission_wait_timeout_s=30.0,
            ):
                t0 = time.monotonic()
                with pytest.raises(dl.DeadlineExceeded):
                    tfs.map_blocks(_double(df), df, timeout_s=0.15)
                assert time.monotonic() - t0 < 5.0
        finally:
            release()
        assert dl.controller().queue_depth() == 0
        assert dl.controller().in_flight_now() == 0

    def test_nested_verbs_take_one_slot(self):
        """limit=1 + a lazy chain (terminal forces internally) + a
        stream (per-chunk reduces) both complete: nested verbs never
        re-enter admission, so small limits cannot deadlock."""
        df = _frame(n=96, blocks=3, seed=9)
        with config.override(
            max_concurrent_verbs=1, admission_queue_limit=0
        ):
            lz = df.lazy().map_blocks(_double(df))
            fetch = dsl.reduce_sum(
                tfs.block(lz, "y", tf_name="y_input"), axes=[0]
            ).named("y")
            r = tfs.reduce_blocks(fetch, lz)
            assert np.isfinite(float(np.asarray(r)))

            chunks = [
                TensorFrame.from_dict(
                    {"x": np.ones(8, dtype=np.float32) * (i + 1)}
                )
                for i in range(4)
            ]
            s = tfs.reduce_blocks_stream(_sum_fetch(df), iter(chunks))
            assert float(np.asarray(s)) == pytest.approx(8 * (1 + 2 + 3 + 4))
        assert dl.controller().in_flight_now() == 0

    def test_retry_after_hint_uses_latency_histogram(self):
        df = _frame()
        for _ in range(3):  # populate verb_seconds
            tfs.map_blocks(_double(df), df)
        mean = dl._mean_verb_seconds()
        assert mean is not None and mean > 0.0
        release = dl.controller().admit("holder", None)
        try:
            with config.override(
                max_concurrent_verbs=1, admission_queue_limit=0
            ):
                with pytest.raises(tfs.OverloadError) as ei:
                    tfs.map_blocks(_double(df), df)
        finally:
            release()
        assert ei.value.retry_after_s == pytest.approx(
            max(0.001, mean), rel=0.5
        )

    def test_healthz_reports_overload(self):
        from tensorframes_tpu.utils.telemetry_http import _healthz_payload

        payload = _healthz_payload()
        assert payload["overloaded"] is False
        assert "admission" in payload
        release = dl.controller().admit("holder", None)
        try:
            with config.override(
                max_concurrent_verbs=1, admission_queue_limit=0
            ):
                payload = _healthz_payload()
                assert payload["overloaded"] is True
                assert payload["degraded"] is True
                assert payload["admission"]["in_flight"] == 1
        finally:
            release()

    def test_admission_gauges_registered(self):
        _, gauges, _ = telemetry.metrics_snapshot()
        assert ("admission_queue_depth", ()) in gauges
        assert ("admission_in_flight", ()) in gauges


# ---------------------------------------------------------------------------
# multi-thread stress (satellite 3)
# ---------------------------------------------------------------------------


class TestConcurrencyStress:
    def test_mixed_verbs_bounded_inflight_no_deadlock(self):
        """N threads x mixed verbs under a small limit: no deadlock,
        in-flight bounded by the limit, zero sheds with a roomy queue,
        and every result bit-identical to the single-threaded
        reference."""
        df = _frame(n=120, blocks=4, seed=21)
        kf = TensorFrame.from_dict(
            {
                "k": np.arange(24, dtype=np.int64) % 3,
                "x": np.arange(24, dtype=np.float32),
            }
        )
        ref_map = np.asarray(tfs.map_blocks(_double(df), df)["y"].values)
        ref_sum = float(np.asarray(tfs.reduce_blocks(_sum_fetch(df), df)))
        ref_agg = np.asarray(
            tfs.aggregate(_sum_fetch(kf), tfs.group_by(kf, "k"))["x"].values
        )

        n_threads = 8
        failures = []
        barrier = threading.Barrier(n_threads)

        def worker(i):
            try:
                barrier.wait(timeout=30.0)
                for _ in range(3):
                    kind = i % 3
                    if kind == 0:
                        got = np.asarray(
                            tfs.map_blocks(_double(df), df)["y"].values
                        )
                        assert np.array_equal(got, ref_map)
                    elif kind == 1:
                        got = float(
                            np.asarray(tfs.reduce_blocks(_sum_fetch(df), df))
                        )
                        assert got == ref_sum
                    else:
                        got = np.asarray(
                            tfs.aggregate(
                                _sum_fetch(kf), tfs.group_by(kf, "k")
                            )["x"].values
                        )
                        assert np.array_equal(got, ref_agg)
            except Exception as e:  # noqa: BLE001 — reported below
                failures.append((i, e))

        dl.controller().reset()
        shed0 = dl.controller().snapshot()["shed"]
        with config.override(
            max_concurrent_verbs=2, admission_queue_limit=16,
            admission_wait_timeout_s=60.0,
        ):
            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120.0)
            assert not any(t.is_alive() for t in threads), "deadlock"
        assert not failures, failures
        snap = dl.controller().snapshot()
        assert snap["peak_in_flight"] <= 2, snap  # bounded in-flight
        assert snap["shed"] == shed0  # roomy queue: nothing shed
        assert snap["in_flight"] == 0

    def test_overload_exact_shed_accounting(self):
        """2x overload against limit 1 / zero queue: every call either
        returns the bit-identical result or sheds with OverloadError —
        and the controller/counter/ledger counts match the caught
        exceptions EXACTLY."""
        df = _frame(n=4096, blocks=4, seed=22)
        ref = float(np.asarray(tfs.reduce_blocks(_sum_fetch(df), df)))
        dl.controller().reset()
        rtf.reset_ledger()
        telemetry.reset_counters()

        n_threads, per_thread = 4, 4
        ok = []
        shed = []
        failures = []
        barrier = threading.Barrier(n_threads)

        def worker(i):
            try:
                barrier.wait(timeout=30.0)
                for _ in range(per_thread):
                    try:
                        got = float(
                            np.asarray(
                                tfs.reduce_blocks(_sum_fetch(df), df)
                            )
                        )
                        assert got == ref
                        ok.append(got)
                    except tfs.OverloadError as e:
                        assert e.limit == 1
                        assert e.retry_after_s > 0.0
                        shed.append(e)
            except Exception as e:  # noqa: BLE001
                failures.append((i, e))

        with config.override(
            max_concurrent_verbs=1, admission_queue_limit=0
        ):
            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120.0)
        assert not any(t.is_alive() for t in threads), "deadlock"
        assert not failures, failures
        total = n_threads * per_thread
        assert len(ok) + len(shed) == total
        assert len(ok) >= 1  # someone always holds the slot
        snap = dl.controller().snapshot()
        assert snap["shed"] == len(shed)  # exact accounting
        assert telemetry.flat_counters().get("verbs_shed", 0) == len(shed)
        assert executor_stats()["faults"]["shed"] == len(shed)
        assert snap["in_flight"] == 0


# ---------------------------------------------------------------------------
# device-grant watchdog honors the verb deadline (satellite 2)
# ---------------------------------------------------------------------------


class TestDeviceGrantDeadline:
    def test_grant_watchdog_clips_to_deadline(self):
        """The deadline (0.25s), tighter than the 30s watchdog, bounds
        the wait — and because the DEADLINE tripped (not the watchdog),
        the verb gets its typed DeadlineExceeded, never the wedged-
        backend CPU fallback."""
        rtf._reset_grant_state()
        wedge = threading.Event()
        try:
            t0 = time.monotonic()
            with dl.verb_scope("t", timeout_s=0.25):
                with pytest.raises(dl.DeadlineExceeded):
                    rtf.device_grant(
                        grab=lambda: wedge.wait(60.0),
                        timeout_s=30.0,
                        fallback=lambda: ["fallback-dev"],
                    )
            elapsed = time.monotonic() - t0
            assert elapsed < 2.0, f"watched the full 30s? {elapsed:.1f}s"
        finally:
            wedge.set()
            rtf._reset_grant_state()

    def test_grant_deadline_arms_disabled_watchdog(self):
        """With the config watchdog OFF, an active deadline still
        bounds the grant — surfacing as the verb's DeadlineExceeded."""
        rtf._reset_grant_state()
        wedge = threading.Event()
        try:
            t0 = time.monotonic()
            with dl.verb_scope("t", timeout_s=0.2):
                with pytest.raises(dl.DeadlineExceeded):
                    rtf.device_grant(
                        grab=lambda: wedge.wait(60.0),
                        timeout_s=None,  # config default: 0 = off
                        fallback=lambda: ["fallback-dev"],
                    )
            assert time.monotonic() - t0 < 2.0
        finally:
            wedge.set()
            rtf._reset_grant_state()

    def test_expired_scope_raises_before_grant(self):
        rtf._reset_grant_state()
        try:
            with dl.verb_scope("t", timeout_s=0.01):
                time.sleep(0.05)
                with pytest.raises(dl.DeadlineExceeded):
                    rtf.device_grant(
                        grab=lambda: ["dev"], timeout_s=5.0,
                        fallback=lambda: ["fb"],
                    )
        finally:
            rtf._reset_grant_state()

    def test_deadline_clipped_grant_never_caches_fallback(self):
        """A grant that outlives one verb's budget is a DEADLINE
        failure, not a wedged backend: it must raise DeadlineExceeded
        (no 'wedged' warning, no fallback) and must NOT poison the
        process-wide fallback cache — the next verb, with a real
        budget, gets the real devices."""
        rtf._reset_grant_state()
        release = threading.Event()

        def slow_grab():
            release.wait(30.0)
            return ["real-dev"]

        try:
            with dl.verb_scope("t", timeout_s=0.15):
                with pytest.raises(dl.DeadlineExceeded):
                    rtf.device_grant(
                        grab=slow_grab, timeout_s=30.0,
                        fallback=lambda: ["cpu-fallback"],
                    )
            # the cache must be clean: un-deadlined call gets the
            # REAL devices once the backend responds
            release.set()
            out = rtf.device_grant(
                grab=slow_grab, timeout_s=30.0,
                fallback=lambda: ["cpu-fallback"],
            )
            assert out == ["real-dev"]
        finally:
            release.set()
            rtf._reset_grant_state()


class TestReviewRegressions:
    def test_default_timeout_applies_under_bare_deadline_scope(self):
        """config.default_verb_timeout_s is a per-unit-of-load safety
        net: wrapping verbs in a bare deadline_scope() (e.g. purely
        for cross-thread cancel()) must not silently drop it."""
        with config.override(default_verb_timeout_s=5.0):
            with tfs.deadline_scope():  # no deadline of its own
                with dl.verb_scope("t") as sc:
                    assert sc.remaining() is not None
                    assert sc.remaining() <= 5.0 + 1e-6
            # and it still tightens against an envelope deadline
            with tfs.deadline_scope(timeout_s=0.5):
                with dl.verb_scope("t") as sc:
                    assert sc.remaining() <= 0.5 + 1e-6

    def test_pipeline_consumer_exits_on_captured_scope_death(self):
        """A pipelined stream whose first pull happened inside a scope
        must not spin forever when that scope dies while later pulls
        run OUTSIDE it (stale captured scope tears stages down without
        an _END): the consumer raises the typed error instead."""
        from tensorframes_tpu.ingest.pipeline import pipelined

        def slow_source():
            for i in range(1000):
                time.sleep(0.02)
                yield i

        got = []
        errs = []

        def consume(gen):
            try:
                for item in gen:
                    got.append(item)
            except (dl.DeadlineExceeded, dl.Cancelled) as e:
                errs.append(e)

        with dl.deadline_scope(timeout_s=0.25):
            gen = pipelined(slow_source(), [])
            got.append(next(gen))  # first pull captures the scope
        # keep consuming OUTSIDE the scope, on another thread (no
        # ambient scope there at all)
        th = threading.Thread(target=consume, args=(gen,))
        th.start()
        th.join(timeout=10.0)
        assert not th.is_alive(), "consumer spun past scope death"
        assert errs, "typed deadline error did not surface"
        assert _wait_ingest_threads_gone()
