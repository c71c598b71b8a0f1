"""Fault-tolerant dispatch runtime (ISSUE 6): classification, classified
retries with backoff, device failover with circuit breaker, OOM block
splitting, the deterministic fault-injection harness, the device-grant
watchdog, and the `_prefetch_iter` failure paths.

Runs on the conftest 8-device virtual CPU mesh; the block scheduler is
auto-on, so failover paths are exercised for real.
"""

import threading
import time

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import config, dsl
from tensorframes_tpu.runtime import faults as rtf
from tensorframes_tpu.runtime.scheduler import (
    BlockSchedule,
    device_health,
)
from tensorframes_tpu.testing import faults as chaos
from tensorframes_tpu.utils.profiling import reset_stats, stats


def _sum_graph(df):
    x_in = tfs.block(df, "x", tf_name="x_input")
    return dsl.reduce_sum(x_in, axes=[0]).named("x")


FAST_RETRY = dict(retry_backoff_base_s=0.001, retry_backoff_max_s=0.002)


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


class TestClassify:
    def test_transient_status_prefixes(self):
        for msg in (
            "UNAVAILABLE: socket closed",
            "INTERNAL: Failed to enqueue program",
            "DATA_LOSS: chip rebooted",
            "ABORTED: device lost",
            "DEADLINE_EXCEEDED: device rpc",
        ):
            assert rtf.classify(RuntimeError(msg)) == rtf.TRANSIENT, msg

    def test_phrases_trusted_only_on_runtime_owned_types(self):
        class XlaRuntimeError(RuntimeError):
            pass

        assert (
            rtf.classify(XlaRuntimeError("worker preempted mid-step"))
            == rtf.TRANSIENT
        )
        assert (
            rtf.classify(ConnectionError("connection reset by peer"))
            == rtf.TRANSIENT
        )
        # the same prose on plain RuntimeError stays deterministic: a
        # status WORD without the absl "CODE:" shape is user prose
        assert (
            rtf.classify(RuntimeError("worker preempted mid-step"))
            == rtf.DETERMINISTIC
        )
        assert (
            rtf.classify(RuntimeError("worker thread aborted"))
            == rtf.DETERMINISTIC
        )

    def test_resource_patterns(self):
        for exc in (
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating"),
            RuntimeError("failed to allocate 2.1G"),
            MemoryError("host"),
        ):
            assert rtf.classify(exc) == rtf.RESOURCE, exc

    def test_deterministic_default(self):
        for exc in (
            FloatingPointError("fetch 'z' contains NaN"),
            ValueError("shape mismatch"),
            TypeError("bad dtype"),
            KeyError("x"),
            # a user ValueError mentioning a status word is NOT retried:
            # only runtime-ish exception families trust message patterns
            ValueError("column UNAVAILABLE in frame"),
        ):
            assert rtf.classify(exc) == rtf.DETERMINISTIC, exc

    def test_tagged_class_wins(self):
        e = ValueError("anything")
        e.tfs_fault_class = rtf.TRANSIENT
        assert rtf.classify(e) == rtf.TRANSIENT

    def test_injected_faults_classify(self):
        e = chaos.InjectedFault("x", rtf.RESOURCE, 0, "block")
        assert rtf.classify(e) == rtf.RESOURCE


class TestBackoff:
    def test_deterministic_and_exponential(self):
        with config.override(
            retry_backoff_base_s=0.1, retry_backoff_max_s=10.0,
            retry_jitter=0.25, retry_seed=3,
        ):
            d1 = rtf.backoff_delay(1, "w")
            d2 = rtf.backoff_delay(2, "w")
            d3 = rtf.backoff_delay(3, "w")
            # deterministic: same inputs, same delays
            assert d1 == rtf.backoff_delay(1, "w")
            # exponential envelope with bounded jitter
            assert 0.1 <= d1 <= 0.1 * 1.25
            assert 0.2 <= d2 <= 0.2 * 1.25
            assert 0.4 <= d3 <= 0.4 * 1.25

    def test_cap(self):
        with config.override(
            retry_backoff_base_s=0.1, retry_backoff_max_s=0.15,
            retry_jitter=0.0,
        ):
            assert rtf.backoff_delay(10, "w") == 0.15


# ---------------------------------------------------------------------------
# injection harness
# ---------------------------------------------------------------------------


class TestInjectionHarness:
    def test_nth_fires_exactly_once(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(32.0)}, num_blocks=4)
        z = (tfs.block(df, "x") + 1.0).named("z")
        ref = np.asarray(tfs.map_blocks(z, df)["z"].values)
        with config.override(**FAST_RETRY):
            with chaos.inject(nth=[1], fault="transient") as plan:
                got = np.asarray(tfs.map_blocks(z, df)["z"].values)
        assert plan.injected == 1
        assert plan.faulted_ordinals == [1]
        np.testing.assert_array_equal(ref, got)

    def test_seeded_rate_is_reproducible(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(256.0)}, num_blocks=8)
        z = (tfs.block(df, "x") * 3.0).named("z")
        runs = []
        for _ in range(2):
            with config.override(
                block_retry_attempts=8, verb_retry_budget=100, **FAST_RETRY
            ):
                with chaos.inject(rate=0.4, seed=11) as plan:
                    tfs.map_blocks(z, df)
            runs.append(list(plan.faulted_ordinals))
            device_health().reset()
        assert runs[0] == runs[1]
        assert runs[0]  # something actually fired at 40%

    def test_kind_filter(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(64.0)}, num_blocks=4)
        with config.override(**FAST_RETRY):
            with chaos.inject(
                rate=1.0, fault="transient", kind="reduce-combine",
                max_faults=1,
            ) as plan:
                out = float(tfs.reduce_blocks(_sum_graph(df), df))
        assert out == float(np.arange(64.0).sum())
        # exactly one fault fired, and only once the combine kind ran —
        # block-kind dispatches (which run first) never matched
        assert plan.injected == 1

    def test_nesting_rejected(self):
        with chaos.inject(nth=[0]):
            with pytest.raises(RuntimeError, match="already active"):
                with chaos.inject(nth=[1]):
                    pass

    def test_max_faults_budget(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(64.0)}, num_blocks=8)
        z = (tfs.block(df, "x") + 1.0).named("z")
        with config.override(
            block_retry_attempts=8, verb_retry_budget=100, **FAST_RETRY
        ):
            with chaos.inject(rate=1.0, max_faults=2) as plan:
                tfs.map_blocks(z, df)
        assert plan.injected == 2


# ---------------------------------------------------------------------------
# classified retries end to end
# ---------------------------------------------------------------------------


class TestClassifiedRetries:
    def test_transient_faults_recover_bit_identical(self):
        rng = np.random.RandomState(0)
        df = tfs.TensorFrame.from_dict(
            {"x": rng.rand(4096).astype(np.float32)}, num_blocks=8
        )
        z = (tfs.block(df, "x") * 2.0 + 1.0).named("z")
        ref_map = np.asarray(tfs.map_blocks(z, df)["z"].values)
        x_in = tfs.block(df, "x", tf_name="x_input")
        gmin = dsl.reduce_min(x_in, axes=[0]).named("x")
        ref_min = float(tfs.reduce_blocks(gmin, df))
        with config.override(
            block_retry_attempts=8, verb_retry_budget=200, **FAST_RETRY
        ):
            with chaos.inject(rate=0.3, seed=7) as plan:
                got_map = np.asarray(tfs.map_blocks(z, df)["z"].values)
                got_min = float(tfs.reduce_blocks(gmin, df))
        assert plan.injected > 0
        np.testing.assert_array_equal(ref_map, got_map)
        assert ref_min == got_min
        led = rtf.ledger_snapshot()
        assert led["transient"] > 0 and led["retries"] > 0

    def test_chained_map_reduce_under_faults_keeps_results_and_syncs(self):
        """30% transient faults over a chained map -> sum/min/max on a
        device-resident frame: the map, min and max are bit-identical
        to the fault-free chain, the sum is within the reassociation
        tolerance, and retrying adds no host sync."""
        rng = np.random.RandomState(0)
        df = tfs.TensorFrame.from_dict(
            {"x": rng.rand(8192).astype(np.float32)}, num_blocks=8
        ).to_device()
        z = (tfs.block(df, "x") * 2.0 + 1.0).named("y")

        def chained():
            mapped = tfs.map_blocks(z, df)
            res = {
                op: float(np.asarray(tfs.reduce_blocks(
                    fn(tfs.block(mapped, "y", tf_name="y_input"),
                       axes=[0]).named("y"),
                    mapped,
                )))
                for op, fn in (("sum", dsl.reduce_sum),
                               ("min", dsl.reduce_min),
                               ("max", dsl.reduce_max))
            }
            return np.asarray(mapped["y"].values), res

        chained()  # compiles out of the counted runs
        reset_stats()
        ref_map, ref = chained()
        syncs_clean = stats().get("host_sync", 0)
        with config.override(
            block_retry_attempts=8, verb_retry_budget=500, **FAST_RETRY
        ):
            reset_stats()
            with chaos.inject(rate=0.3, seed=7, fault="transient") as plan:
                got_map, got = chained()
            syncs_chaos = stats().get("host_sync", 0)
        assert plan.injected > 0
        np.testing.assert_array_equal(ref_map, got_map)
        assert got["min"] == ref["min"] and got["max"] == ref["max"]
        np.testing.assert_allclose(got["sum"], ref["sum"], rtol=1e-5)
        assert syncs_chaos == syncs_clean
        assert rtf.ledger_snapshot()["retries"] > 0

    def test_deterministic_error_single_attempt_e2e(self):
        """check_numerics' FloatingPointError must surface immediately
        even with a big retry budget (the ISSUE-6 regression)."""
        df = tfs.TensorFrame.from_dict(
            {"x": np.array([1.0, 0.0, 4.0])}, num_blocks=1
        )
        x = tfs.block(df, "x")
        z = (x / (x - x)).named("z")  # 0/0 -> nan
        with config.override(check_numerics=True, block_retry_attempts=5):
            t0 = time.perf_counter()
            with pytest.raises(FloatingPointError, match="map_blocks.*'z'"):
                tfs.map_blocks(z, df)
            dt = time.perf_counter() - t0
        # no backoff sleeps happened (base default is 50ms x 5 attempts)
        assert dt < 2.0
        # and nothing was classified transient/retried along the way
        led = rtf.ledger_snapshot()
        assert led["retries"] == 0 and led["transient"] == 0

    def test_injected_deterministic_not_retried(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(8.0)}, num_blocks=1)
        z = (tfs.block(df, "x") + 1.0).named("z")
        with config.override(block_retry_attempts=5):
            with chaos.inject(nth=[0], fault="deterministic") as plan:
                with pytest.raises(chaos.InjectedFault):
                    tfs.map_blocks(z, df)
        assert plan.injected == 1

    def test_verb_budget_bounds_retries(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(64.0)}, num_blocks=4)
        z = (tfs.block(df, "x") + 1.0).named("z")
        with config.override(
            block_retry_attempts=50, verb_retry_budget=3, **FAST_RETRY
        ):
            with chaos.inject(rate=1.0) as plan:
                with pytest.raises(chaos.InjectedFault):
                    tfs.map_blocks(z, df)
        # 1 first attempt + 3 budgeted retries on the first block, then
        # the next failure gives up (budget spent) — bounded, not 50
        assert plan.injected <= 6


# ---------------------------------------------------------------------------
# OOM block splitting
# ---------------------------------------------------------------------------


class TestOomSplit:
    def test_map_split_concatenates(self):
        rng = np.random.RandomState(1)
        df = tfs.TensorFrame.from_dict(
            {"x": rng.rand(1024).astype(np.float32)}, num_blocks=2
        )
        z = (tfs.block(df, "x") * 2.0).named("z")
        ref = np.asarray(tfs.map_blocks(z, df)["z"].values)
        with chaos.inject(nth=[0], fault="resource"):
            got = np.asarray(tfs.map_blocks(z, df)["z"].values)
        np.testing.assert_array_equal(ref, got)
        led = rtf.ledger_snapshot()
        assert led["splits"] >= 1 and led["resource"] >= 1

    def test_reduce_split_monoid_combines(self):
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(512.0, dtype=np.float64)}, num_blocks=2
        )
        ref = float(tfs.reduce_blocks(_sum_graph(df), df))
        with chaos.inject(nth=[0], fault="resource"):
            got = float(tfs.reduce_blocks(_sum_graph(df), df))
        np.testing.assert_allclose(got, ref, rtol=1e-12)
        assert rtf.ledger_snapshot()["splits"] >= 1

    def test_reduce_split_mean_weighted(self):
        # odd row count: the halves have different weights, so an
        # unweighted combine would be wrong
        vals = np.arange(101.0)
        df = tfs.TensorFrame.from_dict({"x": vals}, num_blocks=1)
        x_in = tfs.block(df, "x", tf_name="x_input")
        gmean = dsl.reduce_mean(x_in, axes=[0]).named("x")
        ref = float(tfs.reduce_blocks(gmean, df))
        with chaos.inject(nth=[0], fault="resource"):
            got = float(tfs.reduce_blocks(gmean, df))
        np.testing.assert_allclose(got, ref, rtol=1e-12)
        assert abs(got - float(vals.mean())) < 1e-9

    def test_unclassifiable_reduce_reraises(self):
        """A reduce the chunk classifier rejects cannot split: the
        original resource error must surface exactly."""
        df = tfs.TensorFrame.from_dict({"x": np.arange(64.0)}, num_blocks=1)
        x_in = tfs.block(df, "x", tf_name="x_input")
        # max - min: fetch node is Sub, not a recognized monoid root
        spread = (
            dsl.reduce_max(x_in, axes=[0]) - dsl.reduce_min(x_in, axes=[0])
        ).named("x")
        with chaos.inject(nth=[0], fault="resource"):
            with pytest.raises(chaos.InjectedFault, match="RESOURCE"):
                tfs.reduce_blocks(
                    spread, df, fetch_names=None
                )
        assert rtf.ledger_snapshot()["splits"] == 0

    def test_split_depth_bounded(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(64.0)}, num_blocks=1)
        z = (tfs.block(df, "x") + 1.0).named("z")
        with config.override(oom_split_depth=2):
            with chaos.inject(rate=1.0, fault="resource") as plan:
                with pytest.raises(chaos.InjectedFault):
                    tfs.map_blocks(z, df)
        # 1 + 2 + 4 dispatches at depths 0..2, then depth limit re-raises
        assert plan.injected <= 7

    def test_lazy_fused_reduce_splits(self):
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(256.0)}, num_blocks=2
        )
        ref = float(np.arange(256.0).sum() * 2.0)
        with chaos.inject(nth=[0], fault="resource"):
            lz = tfs.LazyFrame(df)
            z = (tfs.block(lz, "x") * 2.0).named("y")
            fused = tfs.map_blocks(z, lz)
            y_in = tfs.block(fused, "y", tf_name="y_input")
            got = float(
                fused.reduce_blocks(
                    dsl.reduce_sum(y_in, axes=[0]).named("y")
                )
            )
        np.testing.assert_allclose(got, ref, rtol=1e-12)
        assert rtf.ledger_snapshot()["splits"] >= 1


# ---------------------------------------------------------------------------
# device failover + circuit breaker
# ---------------------------------------------------------------------------


class TestDeviceHealth:
    def test_circuit_opens_and_half_open_probe(self):
        h = device_health()
        h.mark_failure("cpu:9", now=100.0)
        assert not h.usable("cpu:9", now=100.1)
        # cooldown elapsed -> half-open probe admitted
        cooldown = h.table()[0]["cooldown_s"]
        assert h.usable("cpu:9", now=100.0 + cooldown + 0.01)
        assert h.table()[0]["state"] == "half-open"
        # probe success closes the circuit
        h.mark_success("cpu:9")
        assert h.table() == []

    def test_half_open_failure_doubles_cooldown(self):
        h = device_health()
        with config.override(device_cooldown_s=10.0):
            h.mark_failure("cpu:9", now=0.0)
            assert h.usable("cpu:9", now=10.5)  # half-open
            h.mark_failure("cpu:9", now=10.5)
            row = h.table()[0]
            assert row["state"] == "open"
            assert row["cooldown_s"] == 20.0
            assert not h.usable("cpu:9", now=20.0)
            assert h.usable("cpu:9", now=31.0)

    def test_resolve_filters_open_circuits(self):
        import jax

        from tensorframes_tpu.runtime import scheduler as rs

        devs = jax.local_devices()
        if len(devs) < 2:
            pytest.skip("needs >1 device")
        device_health().mark_failure(rs.device_label(devs[0]))
        with config.override(block_scheduler="on"):
            out = rs.resolve()
        assert devs[0] not in out
        assert len(out) == len(devs) - 1

    def test_all_open_falls_back_to_full_set(self):
        import jax

        from tensorframes_tpu.runtime import scheduler as rs

        for d in jax.local_devices():
            device_health().mark_failure(rs.device_label(d))
        with config.override(block_scheduler="on"):
            out = rs.resolve()
        assert len(out) == len(jax.local_devices())


class TestFailover:
    def _schedule(self, ndev=4, items=8):
        import jax

        devs = tuple(jax.local_devices()[:ndev])
        if len(devs) < ndev:
            pytest.skip("needs forced multi-device mesh")
        from tensorframes_tpu.runtime import scheduler as rs

        weights = [8, 7, 6, 5, 4, 3, 2, 1][:items]
        return (
            BlockSchedule(
                devs, rs.plan(weights, ndev), weights=weights
            ),
            weights,
        )

    def test_evict_replaces_unissued_items(self):
        sched, weights = self._schedule()
        victim_slot = sched.assignment[0]
        # mark item 1 issued on its device: it must NOT move
        sched._issued[1] = True
        before = list(sched.assignment)
        label = sched.evict(0)
        assert label == sched.labels[victim_slot]
        assert sched.assignment[1] == before[1]
        for i, slot in enumerate(sched.assignment):
            if i == 1:
                continue
            assert slot != victim_slot, (i, sched.assignment)

    def test_evict_deterministic(self):
        s1, _ = self._schedule()
        s2, _ = self._schedule()
        s1.evict(0)
        s2.evict(0)
        assert s1.assignment == s2.assignment

    def test_evict_unscheduled_item_noop(self):
        import jax

        devs = tuple(jax.local_devices()[:2])
        sched = BlockSchedule(devs, [None, 0], weights=[0, 4])
        assert sched.evict(0) is None

    def test_e2e_failover_replaces_blocks(self):
        """Acceptance: injected transient faults on one device evict
        it, and its blocks DEMONSTRABLY re-place onto other devices."""
        import jax

        if len(jax.local_devices()) < 2:
            pytest.skip("needs >1 device")
        from tensorframes_tpu.runtime.executor import Executor

        ex = Executor()
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(4096.0)}, num_blocks=8
        )
        z = (tfs.block(df, "x") + 1.0).named("z")
        ref = np.asarray(tfs.map_blocks(z, df, executor=ex)["z"].values)
        victim = "cpu:0"
        with config.override(
            block_retry_attempts=8, verb_retry_budget=100,
            block_scheduler="on", **FAST_RETRY,
        ):
            with chaos.inject(
                rate=1.0, fault="transient", device=victim, max_faults=1
            ) as plan:
                got = np.asarray(
                    tfs.map_blocks(z, df, executor=ex)["z"].values
                )
        np.testing.assert_array_equal(ref, got)
        assert plan.injected == 1
        assert plan.faulted_devices == [victim]
        assert rtf.ledger_snapshot()["evictions"] >= 1
        # the victim's circuit is open; a fresh verb call schedules
        # around it entirely
        from tensorframes_tpu.utils.inspection import executor_stats

        before = dict(
            executor_stats(ex).get("device_dispatches", {})
        )
        tfs.map_blocks(z, df, executor=ex)
        after = executor_stats(ex)["device_dispatches"]
        assert after.get(victim, 0) == before.get(victim, 0)

    def test_diagnostics_shows_health_and_retries(self):
        """Acceptance: tfs.diagnostics() shows the device-health table
        and nonzero fault_retries after an injected-fault run."""
        df = tfs.TensorFrame.from_dict({"x": np.arange(64.0)}, num_blocks=4)
        z = (tfs.block(df, "x") + 1.0).named("z")
        with config.override(
            block_retry_attempts=4, verb_retry_budget=50, **FAST_RETRY
        ):
            with chaos.inject(nth=[0], fault="transient"):
                tfs.map_blocks(z, df)
        from tensorframes_tpu.utils.telemetry import flat_counters

        counters = flat_counters()
        assert counters.get("fault_retries{class=transient}", 0) >= 1
        text = tfs.diagnostics()
        assert "device health" in text
        assert "faults:" in text
        led = rtf.ledger_snapshot()
        assert led["retries"] >= 1


# ---------------------------------------------------------------------------
# device-grant watchdog
# ---------------------------------------------------------------------------


class TestDeviceGrantWatchdog:
    def setup_method(self):
        rtf._reset_grant_state()

    def teardown_method(self):
        rtf._reset_grant_state()

    def test_fast_grab_passes_through(self):
        out = rtf.device_grant(
            grab=lambda: ["devA", "devB"], timeout_s=5.0,
            fallback=lambda: ["cpu"],
        )
        assert out == ["devA", "devB"]

    def test_wedged_grab_falls_back(self):
        hang = threading.Event()

        def wedged():
            hang.wait(30.0)
            return ["never"]

        t0 = time.perf_counter()
        out = rtf.device_grant(
            grab=wedged, timeout_s=0.1, fallback=lambda: ["cpu0"]
        )
        assert out == ["cpu0"]
        assert time.perf_counter() - t0 < 5.0
        assert rtf.ledger_snapshot()["grant_timeouts"] == 1
        # the fallback is cached: no second watchdog thread, same result
        assert rtf.device_grant(
            grab=wedged, timeout_s=0.1, fallback=lambda: ["cpu1"]
        ) == ["cpu0"]
        hang.set()

    def test_wedged_grab_without_fallback_raises(self):
        # no explicit fallback= -> a typed error naming the budget,
        # never the CPU backend's devices
        hang = threading.Event()
        try:
            with pytest.raises(rtf.DeviceGrantTimeout, match="0.1s"):
                rtf.device_grant(grab=lambda: hang.wait(30.0), timeout_s=0.1)
            assert rtf.ledger_snapshot()["grant_timeouts"] == 1
            assert rtf.classify(rtf.DeviceGrantTimeout("x")) == "deterministic"
        finally:
            hang.set()

    def test_grab_error_propagates(self):
        def broken():
            raise RuntimeError("no backend")

        with pytest.raises(RuntimeError, match="no backend"):
            rtf.device_grant(
                grab=broken, timeout_s=1.0, fallback=lambda: ["cpu"]
            )

    def test_config_env_seed(self):
        import dataclasses

        from tensorframes_tpu.config import Config

        f = [
            fld for fld in dataclasses.fields(Config)
            if fld.name == "device_grant_timeout_s"
        ][0]
        assert f.default_factory() == 0.0  # off by default

    def test_scheduler_path_uses_watchdog(self, monkeypatch):
        calls = {"n": 0}

        def fake_grant(grab=None, timeout_s=None, fallback=None):
            calls["n"] += 1
            return grab()

        from tensorframes_tpu.runtime import scheduler as rs

        monkeypatch.setattr(rtf, "device_grant", fake_grant)
        with config.override(device_grant_timeout_s=5.0):
            devs = rs._local_devices()
        assert calls["n"] == 1 and devs


# ---------------------------------------------------------------------------
# _prefetch_iter failure paths (ISSUE 6 satellite)
# ---------------------------------------------------------------------------


class TestPrefetchFailures:
    def _threads(self):
        return {t.name for t in threading.enumerate() if t.is_alive()}

    def test_producer_error_carries_chunk_index(self):
        from tensorframes_tpu.streaming import _prefetch_iter

        def chunks():
            yield "c0"
            yield "c1"
            raise RuntimeError("bad shard")

        it = _prefetch_iter(chunks(), depth=2)
        got = [next(it), next(it)]
        with pytest.raises(RuntimeError, match="bad shard") as ei:
            next(it)
        assert got == ["c0", "c1"]
        assert ei.value.tfs_chunk_index == 2
        assert ei.value.tfs_pipeline_stage == "producer"

    def test_stager_error_carries_chunk_index(self):
        from tensorframes_tpu.streaming import _prefetch_iter

        def stage(item):
            if item == "c1":
                raise ValueError("transfer died")
            return item.upper()

        it = _prefetch_iter(iter(["c0", "c1", "c2"]), depth=2, stage=stage)
        assert next(it) == "C0"
        with pytest.raises(ValueError, match="transfer died") as ei:
            # drain; c1 fails in the stager
            next(it)
            next(it)
        assert ei.value.tfs_chunk_index == 1
        assert ei.value.tfs_pipeline_stage == "transfer-stage"

    def test_pipeline_threads_exit_after_error(self):
        """Neither pipeline thread may wedge on the bounded queue after
        a failure: an UNBOUNDED producer would otherwise block forever
        on put() and pin its buffered chunks."""
        from tensorframes_tpu.streaming import _prefetch_iter

        def endless():
            i = 0
            while True:
                yield i
                i += 1

        def stage(item):
            if item == 3:
                raise RuntimeError("boom")
            return item

        before = threading.active_count()
        it = _prefetch_iter(endless(), depth=1, stage=stage)
        with pytest.raises(RuntimeError, match="boom"):
            for _ in range(100):
                next(it)
        it.close()  # consumer abandons; cancellation propagates
        deadline = time.time() + 5.0
        while threading.active_count() > before and time.time() < deadline:
            time.sleep(0.01)
        assert threading.active_count() <= before + 1

    def test_consumer_abandon_after_error_drains_buffers(self):
        from tensorframes_tpu.streaming import _prefetch_iter

        produced = []

        def chunks():
            for i in range(50):
                produced.append(i)
                yield i

        it = _prefetch_iter(chunks(), depth=2)
        assert next(it) == 0
        it.close()  # abandon mid-stream
        time.sleep(0.3)
        # the producer observed cancellation: it did NOT run to the end
        assert len(produced) < 50

    def test_stream_error_surfaces_with_context(self):
        def chunks():
            yield tfs.TensorFrame.from_dict({"x": np.arange(8.0)})
            raise RuntimeError("shard 1 unreadable")

        df0 = tfs.TensorFrame.from_dict({"x": np.arange(8.0)})
        g = _sum_graph(df0)
        with pytest.raises(RuntimeError, match="shard 1 unreadable") as ei:
            tfs.reduce_blocks_stream(g, chunks())
        assert getattr(ei.value, "tfs_chunk_index", None) == 1

    def test_injected_transient_decode_fault_retries(self, tmp_path):
        """ISSUE 7: the parallel-decode stage routes through the same
        classified-retry layer as block dispatch — a transient shard
        read fails, retries in place, and the stream completes with the
        ledger showing the retry."""
        from tensorframes_tpu import io as tio

        data = np.arange(48.0, dtype=np.float32)
        for i in range(3):
            tio.write_parquet(
                tfs.TensorFrame.from_dict(
                    {"x": data[i * 16:(i + 1) * 16]}, num_blocks=2
                ),
                str(tmp_path / f"s{i}.parquet"),
            )
        df0 = tfs.TensorFrame.from_dict({"x": data[:1]})
        with config.override(**FAST_RETRY):
            with chaos.inject_stage(stage="decode", nth=[0]) as plan:
                total = tfs.reduce_blocks_stream(
                    _sum_graph(df0),
                    tio.stream_dataset(str(tmp_path), decode_workers=2),
                )
        assert plan.injected == 1
        np.testing.assert_allclose(float(total), data.sum(), rtol=1e-6)
        assert rtf.ledger_snapshot()["retries"] >= 1

    def test_injected_deterministic_decode_fault_fails_fast(self, tmp_path):
        """A corrupt shard is deterministic: exactly one decode attempt,
        and the surfaced error names the shard file and chunk index."""
        from tensorframes_tpu import io as tio

        for i in range(2):
            tio.write_parquet(
                tfs.TensorFrame.from_dict(
                    {"x": np.arange(8.0, dtype=np.float32)}
                ),
                str(tmp_path / f"s{i}.parquet"),
            )
        df0 = tfs.TensorFrame.from_dict(
            {"x": np.arange(1.0, dtype=np.float32)}
        )
        with chaos.inject_stage(
            stage="decode", nth=[1], fault="deterministic"
        ) as plan:
            with pytest.raises(chaos.InjectedFault) as ei:
                tfs.reduce_blocks_stream(
                    _sum_graph(df0),
                    tio.stream_dataset(str(tmp_path), decode_workers=2),
                )
        assert plan.injected == 1
        assert plan.attempts <= 2  # no retry burn on the corrupt shard
        assert ei.value.tfs_pipeline_stage == "decode"
        assert str(ei.value.tfs_shard_path).endswith(".parquet")
        assert rtf.ledger_snapshot()["failfast"] >= 1


# ---------------------------------------------------------------------------
# ledger / stats surfacing
# ---------------------------------------------------------------------------


class TestLedgerSurfacing:
    def test_executor_stats_carries_fault_ledger(self):
        s = tfs.executor_stats()
        assert "faults" in s
        assert set(s["faults"]) >= {
            "transient", "resource", "deterministic", "retries",
            "splits", "evictions", "failfast", "grant_timeouts",
        }

    def test_block_splits_counter(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(64.0)}, num_blocks=1)
        z = (tfs.block(df, "x") + 1.0).named("z")
        with chaos.inject(nth=[0], fault="resource"):
            tfs.map_blocks(z, df)
        from tensorframes_tpu.utils.telemetry import flat_counters

        c = flat_counters()
        assert c.get("block_splits{verb=map_blocks}", 0) >= 1
        assert c.get("fault_retries{class=resource}", 0) >= 1
