"""Multi-device block scheduler (ISSUE 5): data-parallel block dispatch.

The contract under test: with >1 local device (the conftest forces an
8-device virtual CPU mesh) every non-mesh verb spreads its per-block
dispatches across `jax.local_devices()` — size-aware largest-first
placement, deterministic across runs — while results stay bit-identical
to single-device execution for maps/min/max (float sum/mean within the
documented reassociation tolerance), host-sync counts do not grow, and
the placement is observable through dispatch-span ``device`` labels and
the per-device executor ledgers.
"""

import numpy as np
import pytest

import jax

import tensorframes_tpu as tfs
from tensorframes_tpu import dsl
from tensorframes_tpu.runtime import scheduler as rs
from tensorframes_tpu.runtime.executor import Executor
from tensorframes_tpu.utils import telemetry
from tensorframes_tpu.utils.inspection import executor_stats
from tensorframes_tpu.utils.profiling import reset_stats, stats

NDEV = len(jax.local_devices())

multi_device = pytest.mark.skipif(
    NDEV < 2, reason="needs >1 (virtual) local device"
)


class CountingExecutor(Executor):
    """Journals every compiled-program invocation (kind order) like the
    device-residency suite's counting executor; the scheduler ledgers
    (`device_dispatches`) ride the inherited Executor state."""

    def __init__(self):
        super().__init__()
        self.events = []

    def cached(self, kind, graph, fetches, feed_names, make):
        fn = super().cached(kind, graph, fetches, feed_names, make)

        def wrapped(*args, **kwargs):
            self.events.append(kind)
            return fn(*args, **kwargs)

        return wrapped


def _frame(sizes, mod=13, dtype=np.float32):
    n = int(sum(sizes))
    offsets = list(np.cumsum([0] + list(sizes)))
    df = tfs.TensorFrame.from_dict({"x": (np.arange(n) % mod).astype(dtype)})
    return tfs.TensorFrame([df["x"]], offsets)


def _reduce(df_like, op, col="x"):
    ph = tfs.block(df_like, col, tf_name=col + "_input")
    return {
        "sum": dsl.reduce_sum,
        "min": dsl.reduce_min,
        "max": dsl.reduce_max,
        "mean": dsl.reduce_mean,
    }[op](ph, axes=[0]).named(col)


def _dispatch_devices(name_prefix):
    """Device labels of recorded dispatch spans, in span order."""
    return [
        s.attrs.get("device")
        for s in telemetry.spans()
        if s.kind == "dispatch" and s.name.startswith(name_prefix)
    ]


class TestPlan:
    def test_largest_first_least_loaded(self):
        # weights 8,1,7,2: 8->d0, 7->d1, 2->d1 (load 7<8), 1->d1? no:
        # after 8(d0) 7(d1), next largest 2 -> d1 has 7 < 8 -> d1 (9),
        # then 1 -> d0 (8<9) -> d0
        assert rs.plan([8, 1, 7, 2], 2) == [0, 0, 1, 1]

    def test_zero_weight_blocks_unassigned(self):
        assert rs.plan([4, 0, 4], 2) == [0, None, 1]

    def test_deterministic_under_ties(self):
        a = rs.plan([5, 5, 5, 5], 4)
        assert a == rs.plan([5, 5, 5, 5], 4) == [0, 1, 2, 3]

    def test_fewer_blocks_than_devices(self):
        assert rs.plan([3], 8) == [0]

    def test_balances_load(self):
        weights = [100, 90, 80, 10, 10, 10, 10, 10]
        slots = rs.plan(weights, 4)
        load = [0] * 4
        for w, s in zip(weights, slots):
            load[s] += w
        assert max(load) - min(load) <= 100  # LPT: bounded imbalance
        assert set(slots) == {0, 1, 2, 3}

    def test_rejects_zero_devices(self):
        with pytest.raises(ValueError):
            rs.plan([1], 0)


class TestResolve:
    def test_off_disables(self):
        with tfs.config.override(block_scheduler="off"):
            assert rs.resolve() is None

    def test_auto_on_with_multiple_devices(self):
        with tfs.config.override(block_scheduler="auto"):
            devs = rs.resolve()
        if NDEV > 1:
            assert devs is not None and len(devs) == NDEV
        else:
            assert devs is None

    def test_on_schedules_even_one_device(self):
        with tfs.config.override(block_scheduler="on"):
            devs = rs.resolve()
        assert devs is not None and len(devs) == NDEV

    def test_typo_mode_raises(self):
        with tfs.config.override(block_scheduler="yes"):
            with pytest.raises(ValueError, match="block_scheduler"):
                rs.resolve()

    def test_explicit_devices_win_over_off(self):
        with tfs.config.override(block_scheduler="off"):
            devs = rs.resolve(devices=[0])
        assert devs == (jax.local_devices()[0],)

    def test_explicit_empty_devices_rejected(self):
        with pytest.raises(ValueError, match="devices"):
            rs.resolve(devices=[])

    def test_mesh_takes_precedence(self):
        assert rs.resolve(mesh=object()) is None
        with pytest.raises(ValueError, match="mutually exclusive"):
            rs.resolve(devices=[0], mesh=object())

    def test_unsupported_executor_never_scheduled(self):
        class NoSched:
            supports_scheduling = False

        assert rs.resolve(executor=NoSched()) is None
        with pytest.raises(ValueError, match="supports block scheduling"):
            rs.resolve(devices=[0], executor=NoSched())


@multi_device
class TestPlacement:
    def test_deterministic_placement_counting_executor(self):
        ex = CountingExecutor()
        df = _frame([40, 10, 30, 20, 5])
        z = (tfs.block(df, "x") * 2.0).named("z")
        tfs.map_blocks(z, df, executor=ex)
        first = dict(ex.device_dispatches)
        assert sum(first.values()) == 5
        # largest-first over equal devices: every block its own device
        assert len(first) == 5
        tfs.map_blocks(z, df, executor=ex)
        second = dict(ex.device_dispatches)
        # identical placement on the rerun: every count exactly doubles
        assert second == {k: 2 * v for k, v in first.items()}

    def test_spans_carry_device_labels_matching_plan(self):
        telemetry.reset()
        ex = Executor()
        df = _frame([40, 10, 30, 20])
        z = (tfs.block(df, "x") + 1.0).named("z")
        tfs.map_blocks(z, df, executor=ex)
        labels = _dispatch_devices("map_blocks.block")
        expect = rs.plan(df.block_sizes(), NDEV)
        devs = [rs.device_label(d) for d in jax.local_devices()]
        assert labels == [devs[s] for s in expect]

    def test_executor_stats_per_device_counts(self):
        ex = Executor()
        df = _frame([16, 16, 16])
        z = (tfs.block(df, "x") * 3.0).named("z")
        tfs.map_blocks(z, df, executor=ex)
        s = executor_stats(ex)
        assert sum(s["device_dispatches"].values()) == 3
        assert len(s["device_dispatches"]) == 3
        # each device touched compiled its own jit specialization
        assert sum(s["device_compiles"].values()) >= 3
        assert s["jit_shape_compiles"] >= 3

    def test_devices_override_pins(self):
        ex = Executor()
        target = jax.local_devices()[1]
        df = _frame([8, 8, 8])
        z = (tfs.block(df, "x") - 1.0).named("z")
        out = tfs.map_blocks(z, df, executor=ex, devices=[target])
        assert out["z"].values.devices() == {target}
        assert executor_stats(ex)["device_dispatches"] == {
            rs.device_label(target): 3
        }

    def test_diagnostics_renders_device_table(self):
        telemetry.reset()
        df = _frame([32, 8, 16, 24])
        tfs.map_blocks((tfs.block(df, "x") * 1.5).named("z"), df)
        report = tfs.diagnostics()
        assert "devices (block-scheduler dispatch labels" in report
        assert rs.device_label(jax.local_devices()[0]) in report


@multi_device
class TestResults:
    def test_map_bit_identical_and_no_extra_host_sync(self):
        rng = np.random.default_rng(7)
        x = rng.standard_normal(999).astype(np.float32)
        df = tfs.TensorFrame.from_dict({"x": x}, num_blocks=7)
        z = (tfs.block(df, "x") * 1.7 + 0.3).named("z")
        with tfs.config.override(block_scheduler="off"):
            ref = np.asarray(tfs.map_blocks(z, df)["z"].values)
        reset_stats()
        out = tfs.map_blocks(z, df)
        assert stats().get("host_sync", 0) == 0  # concat stays on device
        np.testing.assert_array_equal(ref, np.asarray(out["z"].values))

    @pytest.mark.parametrize("op", ["min", "max"])
    def test_reduce_min_max_bit_identical(self, op):
        rng = np.random.default_rng(3)
        x = rng.standard_normal(500).astype(np.float32)
        df = tfs.TensorFrame.from_dict({"x": x}, num_blocks=6)
        with tfs.config.override(block_scheduler="off"):
            ref = float(tfs.reduce_blocks(_reduce(df, op), df))
        out = float(tfs.reduce_blocks(_reduce(df, op), df))
        assert ref == out

    @pytest.mark.parametrize("op", ["sum", "mean"])
    def test_reduce_float_sum_mean_within_tolerance(self, op):
        rng = np.random.default_rng(5)
        x = rng.standard_normal(4096).astype(np.float32)
        df = tfs.TensorFrame.from_dict({"x": x}, num_blocks=9)
        with tfs.config.override(block_scheduler="off"):
            ref = float(tfs.reduce_blocks(_reduce(df, op), df))
        out = float(tfs.reduce_blocks(_reduce(df, op), df))
        np.testing.assert_allclose(out, ref, rtol=1e-5)

    def test_integer_sum_bit_identical(self):
        df = _frame([33, 1, 60, 6], dtype=np.int64)
        with tfs.config.override(block_scheduler="off"):
            ref = int(tfs.reduce_blocks(_reduce(df, "sum"), df))
        assert int(tfs.reduce_blocks(_reduce(df, "sum"), df)) == ref

    def test_reduce_rows_fold_order_preserved_bitwise(self):
        # the left-fold contract admits no regrouping: scheduled runs
        # must gather partials and fold in block order, so even this
        # non-associative fp sum is BIT-identical to single-device
        from tensorframes_tpu.schema import ScalarType, Shape

        rng = np.random.default_rng(11)
        x = rng.standard_normal(257).astype(np.float32)
        df = tfs.TensorFrame.from_dict({"x": x}, num_blocks=5)
        x1 = dsl.placeholder(ScalarType.float32, Shape(()), name="x_1")
        x2 = dsl.placeholder(ScalarType.float32, Shape(()), name="x_2")
        fold = (x1 + x2).named("x")
        with tfs.config.override(block_scheduler="off"):
            ref = float(tfs.reduce_rows(fold, df))
        assert float(tfs.reduce_rows(fold, df)) == ref

    def test_reduce_rows_single_row_blocks_committed_off_anchor(self):
        # single-row blocks contribute column SLICES as partials — on a
        # frame committed to a non-anchor device those live off-slot,
        # and the scheduled combine must colocate them (regression: the
        # gather must not trust nominal owner slots)
        from tensorframes_tpu.schema import ScalarType, Shape

        x = (np.arange(72) % 9).astype(np.float32)
        base = tfs.TensorFrame.from_dict({"x": x})
        df = tfs.TensorFrame(
            [base["x"]], [0, 1, 40, 41, 72]
        ).to_device(device=jax.local_devices()[-1])
        x1 = dsl.placeholder(ScalarType.float32, Shape(()), name="x_1")
        x2 = dsl.placeholder(ScalarType.float32, Shape(()), name="x_2")
        fold = (x1 + x2).named("x")
        with tfs.config.override(block_scheduler="off"):
            ref = float(tfs.reduce_rows(fold, df))
        assert float(tfs.reduce_rows(fold, df)) == ref

    def test_reduce_rows_single_row_blocks_drain_queue_gauge(self):
        # regression: 1-row blocks take the slice shortcut (no dispatch)
        # and must carry zero planning weight — otherwise their slot's
        # scheduler_queue_depth gauge reports a phantom pending dispatch
        from tensorframes_tpu.schema import ScalarType, Shape

        telemetry.reset()
        x = (np.arange(10) % 7).astype(np.float32)
        base = tfs.TensorFrame.from_dict({"x": x})
        df = tfs.TensorFrame([base["x"]], [0, 5, 9, 10])
        x1 = dsl.placeholder(ScalarType.float32, Shape(()), name="x_1")
        x2 = dsl.placeholder(ScalarType.float32, Shape(()), name="x_2")
        assert float(tfs.reduce_rows((x1 + x2).named("x"), df)) == x.sum()
        _, gauges, _ = telemetry.metrics_snapshot()
        depths = [
            v for (name, _), v in gauges.items()
            if name == "scheduler_queue_depth"
        ]
        assert all(v == 0 for v in depths), gauges

    def test_map_rows_dense_stays_device_resident(self):
        rng = np.random.default_rng(13)
        x = rng.standard_normal(300).astype(np.float32)
        df = tfs.TensorFrame.from_dict({"x": x}, num_blocks=4)
        y = (tfs.row(df, "x") * 2.0).named("y")
        with tfs.config.override(block_scheduler="off"):
            ref = np.asarray(tfs.map_rows(y, df)["y"].values)
        reset_stats()
        out = tfs.map_rows(y, df)
        # the satellite fix: per-block parts concatenate ON device —
        # no hidden per-block D2H sync before a chained verb
        assert stats().get("host_sync", 0) == 0
        assert isinstance(out["y"].values, jax.Array)
        np.testing.assert_array_equal(ref, np.asarray(out["y"].values))

    def test_single_block_frame(self):
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(10.0, dtype=np.float32)}
        )
        z = (tfs.block(df, "x") + 5.0).named("z")
        np.testing.assert_array_equal(
            np.asarray(tfs.map_blocks(z, df)["z"].values),
            np.arange(10.0, dtype=np.float32) + 5.0,
        )
        assert float(tfs.reduce_blocks(_reduce(df, "sum"), df)) == 45.0

    def test_empty_blocks_skipped(self):
        df = _frame([0, 5, 0, 7, 0])
        with tfs.config.override(block_scheduler="off"):
            ref = float(tfs.reduce_blocks(_reduce(df, "min"), df))
        assert float(tfs.reduce_blocks(_reduce(df, "min"), df)) == ref
        out = tfs.map_blocks((tfs.block(df, "x") * 2.0).named("z"), df)
        assert out.nrows == 12

    def test_empty_frame_still_raises(self):
        df = tfs.TensorFrame.from_dict(
            {"x": np.zeros(0, np.float32)}
        )
        with pytest.raises(ValueError, match="empty frame"):
            tfs.reduce_blocks(_reduce(df, "sum"), df)

    def test_lazy_fused_chain_matches_unscheduled(self):
        rng = np.random.default_rng(17)
        x = rng.standard_normal(777).astype(np.float32)
        df = tfs.TensorFrame.from_dict({"x": x}, num_blocks=5)

        def chain(frame):
            with tfs.lazy():
                lf = tfs.map_blocks(
                    (tfs.block(frame, "x") * 2.0).named("a"), frame
                )
            a_in = tfs.block(lf, "a", tf_name="a_input")
            return float(
                lf.reduce_blocks(dsl.reduce_sum(a_in, axes=[0]).named("a"))
            )

        with tfs.config.override(block_scheduler="off"):
            ref = chain(df)
        np.testing.assert_allclose(chain(df), ref, rtol=1e-5)

    def test_function_front_end_matches(self):
        rng = np.random.default_rng(19)
        x = rng.standard_normal(321).astype(np.float32)
        df = tfs.TensorFrame.from_dict({"x": x}, num_blocks=6)
        with tfs.config.override(block_scheduler="off"):
            ref = np.asarray(
                tfs.map_blocks(lambda x: {"d": x * 3}, df)["d"].values
            )
        out = np.asarray(
            tfs.map_blocks(lambda x: {"d": x * 3}, df)["d"].values
        )
        np.testing.assert_array_equal(ref, out)

    def test_outputs_anchor_coherently_across_calls(self):
        # regression: two scheduled maps over DIFFERENT partitionings
        # must not commit their output columns to different devices —
        # a later dispatch feeding both columns into ONE jit call (the
        # segment-plan aggregate, or any verb with the scheduler turned
        # off) would crash on jax's incompatible-devices check
        x = (np.arange(900) % 11).astype(np.float32)
        base = tfs.TensorFrame.from_dict({"x": x})
        ragged = tfs.TensorFrame(
            [base["x"]], list(np.cumsum([0, 500, 50, 50, 100, 200]))
        )
        a = tfs.map_blocks((tfs.block(ragged, "x") * 2.0).named("a"), ragged)
        b = tfs.map_blocks(
            (tfs.block(a, "x") + 1.0).named("b"), a.repartition(3)
        )
        assert b["a"].values.devices() == b["b"].values.devices()
        two_col = (
            tfs.block(b, "a") + tfs.block(b, "b")
        ).named("c")
        with tfs.config.override(block_scheduler="off"):
            out = tfs.map_blocks(two_col, b)  # one jit call, two columns
        np.testing.assert_allclose(  # a = 2x, b = x+1 (reads passthrough x)
            np.asarray(out["c"].values), x * 2.0 + (x + 1.0), rtol=1e-6
        )

    def test_aggregate_exact_plan_matches(self):
        rng = np.random.default_rng(23)
        n = 500
        k = (rng.integers(0, 9, n)).astype(np.int64)
        v = rng.standard_normal(n).astype(np.float32)
        df = tfs.TensorFrame.from_dict({"k": k, "v": v})
        g = dsl.reduce_sum(
            tfs.block(df, "v", tf_name="v_input"), axes=[0]
        ).named("v")
        with tfs.config.override(
            block_scheduler="off", aggregate_segment_fast=False
        ):
            ref = tfs.aggregate(g, df.group_by("k"))
        with tfs.config.override(aggregate_segment_fast=False):
            out = tfs.aggregate(g, df.group_by("k"))
        np.testing.assert_array_equal(
            np.asarray(ref["k"].values), np.asarray(out["k"].values)
        )
        np.testing.assert_allclose(
            np.asarray(ref["v"].values),
            np.asarray(out["v"].values),
            rtol=1e-5,
        )


@multi_device
class TestBucketingInteraction:
    def test_ragged_repartition_bucketed_and_scheduled(self):
        from tensorframes_tpu import shape_policy as sp

        sizes = [37, 5, 61, 12, 90, 3, 44, 28]
        df = _frame(sizes)
        z = (tfs.block(df, "x") * 2.0 + 1.0).named("z")
        with tfs.config.override(block_scheduler="off"):
            ref = np.asarray(tfs.map_blocks(z, df)["z"].values)
        ex = Executor()
        out = np.asarray(tfs.map_blocks(z, df, executor=ex)["z"].values)
        np.testing.assert_array_equal(ref, out)
        # per-device jit specializations: bounded by (rungs touched per
        # device) summed over devices <= min(blocks, ndev * ladder)
        rungs = len(sp.bucket_ladder(max(sizes)))
        assert ex.jit_shape_compiles() <= min(len(sizes), NDEV * rungs)
        # rerun compiles nothing new: placement and buckets repeat
        before = ex.jit_shape_compiles()
        tfs.map_blocks(z, df, executor=ex)
        assert ex.jit_shape_compiles() == before

    def test_masked_reduce_scheduled_matches(self):
        sizes = [37, 5, 61, 12, 90]
        df = _frame(sizes)  # integer-valued floats: sums order-exact
        with tfs.config.override(block_scheduler="off"):
            ref = float(tfs.reduce_blocks(_reduce(df, "sum"), df))
        ex = Executor()
        out = float(tfs.reduce_blocks(_reduce(df, "sum"), df, executor=ex))
        assert out == ref
        kinds = {k[0] for k in ex.cache_keys()}
        assert "block-bucketed" in kinds  # masked program still used


@multi_device
class TestStreaming:
    def test_chunks_land_on_alternating_devices(self):
        telemetry.reset()
        chunks = [
            tfs.TensorFrame.from_dict(
                {"x": np.full(50 + 3 * i, float(i), np.float32)}
            )
            for i in range(6)
        ]
        g = dsl.reduce_sum(
            tfs.block(chunks[0], "x", tf_name="x_input"), axes=[0]
        ).named("x")
        total = float(tfs.reduce_blocks_stream(g, iter(chunks)))
        expect = sum(float(i) * (50 + 3 * i) for i in range(6))
        assert total == expect
        labels = [
            d for d in _dispatch_devices("reduce_blocks.block") if d
        ]
        devs = [rs.device_label(d) for d in jax.local_devices()]
        # chunk k pinned to device k % ndev (one block per chunk); the
        # final combine over stacked partials may append one more
        # scheduled dispatch of its own
        assert labels[:6] == [devs[i % NDEV] for i in range(6)]
        assert len(labels) <= 7

    def test_stream_explicit_single_device_pin_honored(self):
        telemetry.reset()
        target = jax.local_devices()[3]
        chunks = [
            tfs.TensorFrame.from_dict({"x": np.ones(20, np.float32)})
            for _ in range(3)
        ]
        g = dsl.reduce_sum(
            tfs.block(chunks[0], "x", tf_name="x_input"), axes=[0]
        ).named("x")
        total = tfs.reduce_blocks_stream(g, iter(chunks), devices=[target])
        assert float(total) == 60.0
        labels = [
            d for d in _dispatch_devices("reduce_blocks.block") if d
        ]
        # regression: a one-device list must PIN every chunk (and the
        # final combine), not silently fall back to auto scheduling
        assert labels and set(labels) == {rs.device_label(target)}

    def test_stream_with_empty_chunks_keeps_rotation_and_result(self):
        chunks = [
            tfs.TensorFrame.from_dict({"x": np.ones(10, np.float32)}),
            tfs.TensorFrame.from_dict({"x": np.zeros(0, np.float32)}),
            tfs.TensorFrame.from_dict({"x": np.ones(20, np.float32)}),
        ]
        g = dsl.reduce_sum(
            tfs.block(chunks[0], "x", tf_name="x_input"), axes=[0]
        ).named("x")
        assert float(tfs.reduce_blocks_stream(g, iter(chunks))) == 30.0


@multi_device
class TestHostSyncDiscipline:
    def test_chained_map_reduce_zero_host_syncs(self):
        rng = np.random.default_rng(29)
        x = rng.standard_normal(2048).astype(np.float32)
        df = tfs.TensorFrame.from_dict({"x": x}, num_blocks=8).to_device()
        reset_stats()
        z = (tfs.block(df, "x") * 2.0).named("z")
        mid = tfs.map_blocks(z, df)
        g = dsl.reduce_sum(
            tfs.block(mid, "z", tf_name="z_input"), axes=[0]
        ).named("z")
        res = tfs.reduce_blocks(g, mid)
        assert stats().get("host_sync", 0) == 0  # nothing fetched yet
        assert isinstance(res, jax.Array)


# ---------------------------------------------------------------------------
# the schedule's books (ISSUE 38): kept on the schedule a block, handed to
# the counters once a call; the way back is one `frame.gather` a column
# ---------------------------------------------------------------------------

FOUR = jax.local_devices()[:4]
four_devices = pytest.mark.skipif(NDEV < 4, reason="needs 4 (virtual) devices")
_BOOKS = ("dispatches", "rows", "put_seconds", "bytes_in")


def _resident(k, n=10, device=None):
    """k equal blocks of n float32 rows, off their rung (16 for 10), of
    a column resident on ``device`` (None: uncommitted on the first)."""
    import jax.numpy as jnp

    x = jnp.arange(float(k * n), dtype=jnp.float32)
    if device is not None:
        x = jax.device_put(x, device)
    return tfs.TensorFrame(
        [tfs.Column("x", x)], [n * i for i in range(k + 1)]
    )


def _books():
    """{book: {device label: value}} and the two unlabelled counters."""
    flat = telemetry.flat_counters()
    out = {b: {} for b in _BOOKS}
    for key, v in flat.items():
        for b in _BOOKS:
            head = f"scheduler.{b}{{device="
            if key.startswith(head):
                out[b][key[len(head):-1]] = v
    out["bytes_back"] = flat.get("scheduler.bytes_back", 0.0)
    out["gather_seconds"] = flat.get("scheduler.gather_seconds", 0.0)
    return out


def _queue_depths():
    _, gauges, _ = telemetry.metrics_snapshot()
    return {
        dict(labels)["device"]: v for (name, labels), v in gauges.items()
        if name == "scheduler_queue_depth"
    }


@four_devices
class TestBooks:
    @pytest.mark.parametrize("k", [8, 16])
    @pytest.mark.parametrize("outputs", [1, 2])
    def test_the_rule_of_the_block_loop(self, k, outputs, monkeypatch):
        """A scheduled `map_blocks` records, a call, the span names it
        recorded before ISSUE 38 and `frame.gather`, nothing else: one
        gather an output column, and NO span a block beyond the three
        that were there. What it hands the registry under `scheduler.*`
        and the queue-depth gauge is the same number of writes for 8
        blocks as for 16: once a call, not once a block."""
        df = _resident(k)
        x = tfs.block(df, "x")
        fetch = [(x + 3.0).named("z"), (x * 2.0).named("w")][:outputs]
        tfs.map_blocks(fetch, df, devices=FOUR)  # compiles
        telemetry.reset()
        writes = {"counter": 0, "gauge": 0}
        reg = telemetry._registry
        real_inc, real_set = reg.counter_inc, reg.gauge_set

        def counter_inc(name, value=1.0, **labels):
            writes["counter"] += name.startswith("scheduler.")
            return real_inc(name, value, **labels)

        def gauge_set(name, value, **labels):
            writes["gauge"] += name == "scheduler_queue_depth"
            return real_set(name, value, **labels)

        monkeypatch.setattr(reg, "counter_inc", counter_inc)
        monkeypatch.setattr(reg, "gauge_set", gauge_set)
        tfs.map_blocks(fetch, df, devices=FOUR)
        names = [s.name for s in telemetry.spans()]
        once = ["map_blocks", "map_blocks.plan", "graph.analyze",
                "frame.match", "executor.lookup", "shape.classify",
                "scheduler.plan", "map_blocks.blocks"]
        want = {n: 1 for n in once}
        want.update({"shape.pad": k, "map_blocks.block": k, "shape.unpad": k,
                     "frame.concat": outputs, "frame.gather": outputs})
        assert {n: names.count(n) for n in set(names)} == want
        # the parent's count plus one an output column
        assert len(names) == len(once) + 3 * k + outputs + outputs
        assert writes == {"counter": 4 * len(FOUR) + 2 * outputs,
                          "gauge": len(FOUR)}
        by_id = {s.span_id: s for s in telemetry.spans()}
        for s in telemetry.spans():
            if s.name == "frame.gather":
                assert s.kind == "transfer"
                assert by_id[s.parent_id].name == "frame.concat"
                assert s.attrs["anchor"] == rs.device_label(FOUR[0])
                assert s.attrs["parts"] == k
                assert s.attrs["moved_parts"] == k - k // 4

    def test_the_books_by_arithmetic(self):
        k, n, rung = 8, 10, 16
        df = _resident(k, n)
        z = (tfs.block(df, "x") + 3.0).named("z")
        ex = Executor()
        out = tfs.map_blocks(z, df, devices=FOUR, executor=ex)
        np.testing.assert_array_equal(
            np.asarray(out["z"].values), np.arange(k * n, dtype=np.float32) + 3
        )
        labels = [rs.device_label(d) for d in FOUR]
        b = _books()
        # two blocks a device; the windows of three devices leave the
        # first (a rung's rows of float32 each), their parts come back
        assert b["rows"] == {lab: 2.0 * n for lab in labels}
        assert b["dispatches"] == {lab: 2.0 for lab in labels}
        assert b["bytes_in"] == {
            lab: (0.0 if lab == labels[0] else 2.0 * rung * 4) for lab in labels
        }
        assert b["bytes_back"] == 6 * n * 4
        assert sum(b["dispatches"].values()) == len(
            _dispatch_devices("map_blocks.block")
        )
        assert all(v > 0 for v in b["put_seconds"].values())
        (gather,) = [s for s in telemetry.spans() if s.name == "frame.gather"]
        assert gather.attrs["bytes"] == b["bytes_back"]
        assert b["gather_seconds"] == pytest.approx(gather.seconds)
        assert executor_stats(ex)["device_dispatches"] == {
            lab: 2 for lab in labels
        }
        assert _queue_depths() == {lab: 0.0 for lab in labels}
        # the device lines: issue time, not "busy"; the books beside it
        devices = tfs.diagnostics(format="json")["devices"]
        assert sorted(devices) == sorted(labels)
        for lab in labels:
            assert set(devices[lab]) == {
                "issue_s", "dispatches", "rows", "bytes_in", "put_s"}
            assert devices[lab]["rows"] == 2 * n
            assert devices[lab]["bytes_in"] == b["bytes_in"][lab]
        assert " issue=" in tfs.diagnostics() and "busy=" not in tfs.diagnostics()

    @pytest.mark.parametrize("mode", ["pinned", "on"])
    def test_one_device_books_no_bytes_and_no_seconds(self, mode):
        """A column committed to the schedule's one device: every feed
        is in place, nothing is put, nothing comes back."""
        dev = FOUR[1]
        df = _resident(4, device=dev)
        z = (tfs.block(df, "x") + 3.0).named("z")
        if mode == "pinned":
            out = tfs.map_blocks(z, df, devices=[dev])
        else:
            with tfs.config.override(block_scheduler="on"):
                out = tfs.map_blocks(z, df, devices=[dev])
        assert out["z"].values.devices() == {dev}
        b, lab = _books(), rs.device_label(dev)
        assert b["dispatches"] == {lab: 4.0} and b["rows"] == {lab: 40.0}
        assert b["bytes_in"] == {lab: 0.0} and b["put_seconds"] == {lab: 0.0}
        assert b["bytes_back"] == 0.0 and b["gather_seconds"] == 0.0
        assert "frame.gather" not in [s.name for s in telemetry.spans()]

    @pytest.mark.parametrize(
        "feed", ["committed-here", "uncommitted-here", "elsewhere", "numpy"]
    )
    def test_a_feed_in_place_is_passed_on_and_others_count(self, feed):
        import jax.numpy as jnp

        dev = FOUR[2]
        host = np.arange(6, dtype=np.float32)
        if feed == "numpy":
            x = host
        elif feed == "uncommitted-here":
            with jax.default_device(dev):
                x = jnp.arange(6, dtype=jnp.float32)
            assert not x.committed and x.devices() == {dev}
        else:
            x = jax.device_put(host, dev if feed == "committed-here" else FOUR[0])
        sched = rs.BlockSchedule((dev,), [0], weights=[6])
        seen = []

        def fn(a):
            seen.append(a)
            return a

        sched.bind(0, fn)(x)
        (got,) = seen
        assert got.devices() == {dev} and got.committed
        b, lab = _books(), rs.device_label(dev)
        assert b["dispatches"] == {lab: 1.0} and b["rows"] == {lab: 6.0}
        if feed == "committed-here":
            assert got is x  # not put again, not copied
            assert b["bytes_in"] == {lab: 0.0}
            assert b["put_seconds"] == {lab: 0.0}
        else:
            assert got is not x and b["put_seconds"][lab] > 0
            moved = feed in ("elsewhere", "numpy")  # a numpy feed whole
            assert b["bytes_in"] == {lab: 24.0 if moved else 0.0}
        np.testing.assert_array_equal(np.asarray(got), host)

    def test_flush_twice_hands_over_nothing_twice(self):
        dev0, dev1 = FOUR[:2]
        sched = rs.BlockSchedule((dev0, dev1), [0, 1, 0], weights=[4, 3, 2])
        feed = np.ones(4, np.float32)
        sched.put(0, [feed])
        sched.flush()  # a part of the plan: what went out so far
        lab0, lab1 = rs.device_label(dev0), rs.device_label(dev1)
        b = _books()
        assert b["dispatches"] == {lab0: 1.0, lab1: 0.0}
        assert b["rows"] == {lab0: 4.0, lab1: 0.0}
        assert _queue_depths() == {lab0: 1.0, lab1: 1.0}
        sched.flush()
        assert _books() == b
        sched.put(1, [feed])
        sched.put(2, [feed])  # the plan's last dispatch flushes by itself
        b = _books()
        assert b["dispatches"] == {lab0: 2.0, lab1: 1.0}
        assert b["rows"] == {lab0: 6.0, lab1: 3.0}
        assert b["bytes_in"] == {lab0: 32.0, lab1: 16.0}
        assert _queue_depths() == {lab0: 0.0, lab1: 0.0}
        sched.flush()
        sched.flush()
        assert _books() == b

    def test_rows_are_not_booked_where_the_plan_has_none(self):
        sched = rs.BlockSchedule((FOUR[0],), [0, 0])  # no weights given
        sched.put(0, [np.ones(2, np.float32)])
        sched.put(1, [np.ones(2, np.float32)])
        lab = rs.device_label(FOUR[0])
        assert _books()["rows"] == {lab: 0.0}
        assert _books()["dispatches"] == {lab: 2.0}

    def test_a_call_that_raises_leaves_what_was_issued_and_what_was_not(self):
        from tensorframes_tpu.testing import faults as chaos

        k, j = 8, 5
        df = _resident(k)
        z = (tfs.block(df, "x") + 3.0).named("z")
        ex = Executor()
        tfs.map_blocks(z, df, devices=FOUR, executor=ex)  # compiles
        telemetry.reset()
        with chaos.inject(nth=[j], fault="deterministic", kind="block"):
            with pytest.raises(Exception):
                tfs.map_blocks(z, df, devices=FOUR, executor=ex)
        plan = rs.plan(df.block_sizes(), len(FOUR))
        labels = [rs.device_label(d) for d in FOUR]
        issued = {lab: 0.0 for lab in labels}
        left = {lab: 0.0 for lab in labels}
        for i, s in enumerate(plan):
            (issued if i < j else left)[labels[s]] += 1
        b = _books()
        assert b["dispatches"] == issued
        assert b["rows"] == {lab: 10 * v for lab, v in issued.items()}
        assert _queue_depths() == left and sum(left.values()) == k - j
        assert b["bytes_back"] == 0.0  # it never came to the concat

    @pytest.mark.parametrize("verb", ["reduce_rows", "aggregate"])
    def test_a_put_path_verb_flushes_by_itself(self, verb):
        if verb == "reduce_rows":
            from tensorframes_tpu.schema import ScalarType, Shape

            x = (np.arange(40) % 7).astype(np.float32)
            base = tfs.TensorFrame.from_dict({"x": x})
            df = tfs.TensorFrame([base["x"]], [0, 10, 20, 21, 40])
            x1 = dsl.placeholder(ScalarType.float32, Shape(()), name="x_1")
            x2 = dsl.placeholder(ScalarType.float32, Shape(()), name="x_2")
            got = tfs.reduce_rows((x1 + x2).named("x"), df, devices=FOUR)
            assert float(got) == x.sum()
            dispatches, rows = 3, 39  # the one-row block is never put
        else:
            # the chunked plan (`sched.put` of each chunk size's feeds):
            # more distinct group sizes than the exact plan takes
            keys = np.repeat(np.arange(6), [5, 5, 5, 3, 3, 2]).astype(np.int64)
            vals = np.arange(23, dtype=np.float32)
            df = tfs.TensorFrame.from_dict({"k": keys, "x": vals})
            g = dsl.reduce_sum(
                tfs.block(df, "x", tf_name="x_input"), axes=[0]
            ).named("x")
            with tfs.config.override(
                aggregate_segment_fast=False, aggregate_exact_size_limit=1
            ):
                out = tfs.aggregate(g, tfs.group_by(df, "k"), devices=FOUR)
            assert stats()["aggregate.plan.chunk"] == 1
            np.testing.assert_allclose(
                np.asarray(out["x"].values), np.bincount(keys, weights=vals)
            )
            issued = [
                s for s in telemetry.spans()
                if s.kind == "dispatch" and s.attrs.get("device")
            ]
            assert {s.name for s in issued} == {"aggregate.chunk"}
            dispatches = len(issued)
            rows = sum(s.attrs["rows"] for s in issued)
        b = _books()
        assert sum(b["dispatches"].values()) == dispatches
        assert sum(b["rows"].values()) == rows
        assert set(_queue_depths().values()) == {0.0}

    def test_telemetry_off_no_gather_span_and_the_counters_live(self):
        df = _resident(8)
        z = (tfs.block(df, "x") + 3.0).named("z")
        with tfs.config.override(telemetry=False):
            tfs.map_blocks(z, df, devices=FOUR)
            assert telemetry.spans() == []
        b = _books()
        assert sum(b["rows"].values()) == 80 and b["bytes_back"] == 240
        assert b["gather_seconds"] > 0
        assert _queue_depths() == {}  # the gauge keeps the master switch

    def test_by_device_gives_issue_time_dispatches_and_rows(self):
        ms = 1e-3

        def sp(i, dev, t0, t1, rows):
            return telemetry.Span(
                i, None, "map_blocks.block", "dispatch", t0 * ms, t1 * ms, 0,
                {"device": dev, "rows": rows},
            )

        agg = telemetry.span_aggregates(
            [sp(1, "tpu:0", 0, 2, 10), sp(2, "tpu:0", 1, 4, 10),
             sp(3, "tpu:1", 5, 6, 7)]
        )
        assert agg["by_device"] == {
            "tpu:0": {"issue_s": pytest.approx(4 * ms), "dispatches": 2,
                      "rows": 20.0},
            "tpu:1": {"issue_s": pytest.approx(1 * ms), "dispatches": 1,
                      "rows": 7.0},
        }


# ---------------------------------------------------------------------------
# the home plan (ISSUE 39): a row-local map stays on the device that holds
# its columns, where the scheduler chose the devices itself; everything
# else is planned as before
# ---------------------------------------------------------------------------


def _labels(devices=None):
    return [rs.device_label(d) for d in devices or jax.local_devices()]


def _plus_three(df):
    return (tfs.block(df, "x") + 3.0).named("z")


def _scheduler_writes(monkeypatch):
    """Counts, from here on, the registry writes under ``scheduler.*``
    and of the queue-depth gauge."""
    writes = {"counter": 0, "gauge": 0}
    reg = telemetry._registry
    real_inc, real_set = reg.counter_inc, reg.gauge_set

    def counter_inc(name, value=1.0, **labels):
        writes["counter"] += name.startswith("scheduler.")
        return real_inc(name, value, **labels)

    def gauge_set(name, value, **labels):
        writes["gauge"] += name == "scheduler_queue_depth"
        return real_set(name, value, **labels)

    monkeypatch.setattr(reg, "counter_inc", counter_inc)
    monkeypatch.setattr(reg, "gauge_set", gauge_set)
    return writes


def _two_devices_frame():
    import jax.numpy as jnp

    x = jax.device_put(jnp.arange(80.0, dtype=jnp.float32), FOUR[0])
    y = jax.device_put(jnp.ones(80, dtype=jnp.float32), FOUR[1])
    return tfs.TensorFrame(
        [tfs.Column("x", x), tfs.Column("y", y)], [10 * i for i in range(9)]
    )


def _bound_fetch(df):
    from tensorframes_tpu.schema import ScalarType, Shape

    w = dsl.placeholder(ScalarType.float32, Shape(()), name="w")
    return (tfs.block(df, "x") * w).named("z")


def _minus_its_sum(x):
    return (x - dsl.reduce_sum(x, axes=[0])).named("z")


_X_PLUS_3 = [3.0, 4.0, 5.0]

# case -> (frame, what to call on it, the devices its plan is LPT over
# (None: every local device), the dispatch spans' verb, the output's
# first three rows); every frame has 8 blocks of 10 rows
_AS_TODAY = {
    "explicit-devices": (
        lambda: _resident(8),
        lambda df: tfs.map_blocks(_plus_three(df), df, devices=FOUR),
        FOUR, "map_blocks", _X_PLUS_3,
    ),
    "numpy-column": (
        lambda: _frame([10] * 8),
        lambda df: tfs.map_blocks(_plus_three(df), df), None, "map_blocks", _X_PLUS_3,
    ),
    "two-columns-on-two-devices": (
        _two_devices_frame,
        lambda df: tfs.map_blocks(
            (tfs.block(df, "x") * 2.0 + tfs.block(df, "y")).named("z"), df
        ),
        None, "map_blocks", [1.0, 3.0, 5.0],
    ),
    "a-graph-with-a-reduction": (
        lambda: _resident(8),
        lambda df: tfs.map_blocks(_minus_its_sum(tfs.block(df, "x")), df),
        None, "map_blocks", [-45.0, -44.0, -43.0],
    ),
    "trim": (
        lambda: _resident(8),
        lambda df: tfs.map_blocks(_plus_three(df), df, trim=True),
        None, "map_blocks", _X_PLUS_3,
    ),
    "bindings": (
        lambda: _resident(8),
        lambda df: tfs.map_blocks(
            _bound_fetch(df), df, bindings={"w": np.float32(2.0)}
        ),
        None, "map_blocks", [0.0, 2.0, 4.0],
    ),
    "map_rows": (
        lambda: _resident(8),
        lambda df: tfs.map_rows((tfs.row(df, "x") + 3.0).named("z"), df),
        None, "map_rows", _X_PLUS_3,
    ),
    "function-front-end": (
        lambda: _resident(8),
        lambda df: tfs.map_blocks(lambda x: {"z": x + 3.0}, df),
        None, "map_blocks", _X_PLUS_3,
    ),
    "home-circuit-open": (
        lambda: _resident(8),
        lambda df: tfs.map_blocks(_plus_three(df), df),
        "healthy", "map_blocks", _X_PLUS_3,
    ),
}


@four_devices
class TestHomePlan:
    @pytest.mark.parametrize("where", ["first-uncommitted", "last-committed"])
    @pytest.mark.parametrize("mode", ["auto", "on"])
    def test_a_row_local_map_stays_where_its_column_is(
        self, mode, where, monkeypatch
    ):
        """One group a call on the column's device (the last one as
        well as the anchor), nothing put, nothing gathered; what a call
        records and hands the registry is the same for 8 blocks as for
        16; the output lives with the column, bit-equal to no schedule."""
        home = None if where == "first-uncommitted" else jax.local_devices()[-1]
        calls, seen = 2, {}
        for k in (8, 16):
            df = _resident(k, device=home)
            (dev,) = df["x"].values.devices()
            z, ex = _plus_three(df), Executor()
            with tfs.config.override(block_scheduler="off"):
                want = np.asarray(tfs.map_blocks(z, df)["z"].values)
            with tfs.config.override(block_scheduler=mode):
                tfs.map_blocks(z, df, executor=ex)  # compiles
                telemetry.reset()
                writes = _scheduler_writes(monkeypatch)
                for _ in range(calls):
                    out = tfs.map_blocks(z, df, executor=ex)
                monkeypatch.undo()
            assert out["z"].values.devices() == {dev}
            np.testing.assert_array_equal(np.asarray(out["z"].values), want)
            names = [s.name for s in telemetry.spans()]
            seen[k] = ({n: names.count(n) for n in set(names)}, dict(writes))
            flat, b, lab = telemetry.flat_counters(), _books(), rs.device_label(dev)
            assert flat["shape_bucketing.group_dispatch"] == calls
            assert flat["shape_bucketing.grouped_blocks"] == calls * k
            assert "shape_bucketing.window_dispatch" not in flat
            assert flat["scheduler.home_plans"] == calls
            assert flat["scheduler.home_blocks"] == calls * k
            others = [x for x in _labels() if x != lab]
            assert b["rows"] == {lab: 10.0 * k * calls, **{o: 0.0 for o in others}}
            assert b["dispatches"] == {lab: 1.0 * calls, **{o: 0.0 for o in others}}
            assert not any(b["bytes_in"].values())
            assert not any(b["put_seconds"].values())
            assert b["bytes_back"] == 0.0 and b["gather_seconds"] == 0.0
            assert _dispatch_devices("map_blocks.block") == [lab] * calls
            assert set(_queue_depths().values()) == {0.0}
            assert executor_stats(ex)["device_dispatches"] == {lab: 1 + calls}
        want_names = {n: calls for n in (
            "map_blocks", "map_blocks.plan", "graph.analyze", "frame.match",
            "executor.lookup", "shape.classify", "scheduler.plan",
            "map_blocks.blocks", "map_blocks.block",
        )}
        want_writes = {"counter": calls * (4 * NDEV + 2), "gauge": calls * NDEV}
        assert seen[8] == seen[16] == (want_names, want_writes)
        report = tfs.diagnostics()
        assert f"home plans: {calls} call(s) kept {calls * 16} block(s)" in report
        assert tfs.diagnostics(format="json")["scheduler"] == {
            "home_plans": calls, "home_blocks": calls * 16}

    @pytest.mark.parametrize("case", sorted(_AS_TODAY))
    def test_planned_as_before(self, case):
        """What has no home, what may be worth moving and what the user
        placed: LPT over the rows, block for block, and no home plan."""
        make, call, over, verb, first3 = _AS_TODAY[case]
        df = make()
        devices = jax.local_devices() if over is None else over
        if over == "healthy":
            (home,) = df["x"].values.devices()
            rs.device_health().mark_failure(rs.device_label(home))
            devices = [d for d in jax.local_devices() if d != home]
        telemetry.reset()
        with tfs.config.override(block_scheduler="auto"):
            out = call(df)
        np.testing.assert_array_equal(np.asarray(out["z"].values)[:3], first3)
        labels = _labels(devices)
        expect = rs.plan(df.block_sizes(), len(devices))
        assert _dispatch_devices(f"{verb}.block") == [labels[s] for s in expect]
        assert len(set(expect)) == min(8, len(devices))
        flat = telemetry.flat_counters()
        assert "scheduler.home_plans" not in flat
        assert "scheduler.home_blocks" not in flat
        assert "shape_bucketing.group_dispatch" not in flat
        assert tfs.diagnostics(format="json")["scheduler"] == {
            "home_plans": 0, "home_blocks": 0}
        assert "home plans:" not in tfs.diagnostics()

    @pytest.mark.parametrize("where", ["first-uncommitted", "last-committed"])
    def test_unequal_neighbours_run_block_by_block_at_home(self, where):
        """No run to group: every block a window on the home device,
        nothing moved, the parts joined there without a gather."""
        import jax.numpy as jnp

        x = jnp.arange(38.0, dtype=jnp.float32)
        if where == "last-committed":
            x = jax.device_put(x, jax.local_devices()[-1])
        (dev,) = x.devices()
        df = tfs.TensorFrame([tfs.Column("x", x)], [0, 10, 19, 29, 38])
        z = _plus_three(df)
        with tfs.config.override(block_scheduler="off"):
            want = np.asarray(tfs.map_blocks(z, df)["z"].values)
        telemetry.reset()
        out = tfs.map_blocks(z, df, executor=Executor())
        np.testing.assert_array_equal(np.asarray(out["z"].values), want)
        assert out["z"].values.devices() == {dev}
        lab, flat, b = rs.device_label(dev), telemetry.flat_counters(), _books()
        assert _dispatch_devices("map_blocks.block") == [lab] * 4
        assert flat["shape_bucketing.window_dispatch"] == 4
        assert "shape_bucketing.group_dispatch" not in flat
        assert (flat["scheduler.home_plans"], flat["scheduler.home_blocks"]) == (1, 4)
        assert b["rows"][lab] == 38 and sum(b["rows"].values()) == 38
        assert b["dispatches"][lab] == 4 and sum(b["dispatches"].values()) == 4
        assert not any(b["bytes_in"].values()) and b["bytes_back"] == 0.0
        names = [s.name for s in telemetry.spans()]
        assert names.count("frame.concat") == 1 and "frame.gather" not in names

    def test_a_deadline_in_the_group_carries_the_plans_progress(self):
        from tensorframes_tpu.runtime import deadline as dl
        from tensorframes_tpu.testing import faults as chaos

        df = _resident(8)
        z = _plus_three(df)
        tfs.map_blocks(z, df)  # compiles
        with chaos.inject(nth=[0], fault="hang", delay_s=10.0):
            with pytest.raises(dl.DeadlineExceeded) as hung:
                tfs.map_blocks(z, df, timeout_s=0.3)
        assert hung.value.tfs_blocks_issued == 0
        assert hung.value.tfs_blocks_unissued == 8
        (lab,) = _labels(df["x"].values.devices())
        assert _queue_depths()[lab] == 8.0  # what the call never issued

    def test_the_plan_itself(self):
        """`schedule_weights`: the home's slot for every item with rows,
        None for an empty one; a home outside the set, or an explicit
        device list, is LPT."""
        sizes = [5, 0, 7, 7, 0, 3]
        with tfs.config.override(block_scheduler="on"):
            sched = rs.schedule_weights(sizes, home=FOUR[2])
            assert sched.devices == tuple(jax.local_devices())
            assert sched.assignment == [2, None, 2, 2, None, 2]
            assert sched.at_home(0, 6, FOUR[2])
            assert not sched.at_home(0, 6, FOUR[1])
            pinned = rs.schedule_weights(sizes, devices=FOUR, home=FOUR[2])
            assert pinned.assignment == rs.plan(sizes, 4)
            assert not pinned.at_home(0, 1, FOUR[2])
            away = rs.schedule_weights(sizes, devices=FOUR[:2], home=FOUR[2])
            assert away.assignment == rs.plan(sizes, 2)
        with tfs.config.override(block_scheduler="off"):
            assert rs.schedule_weights(sizes, home=FOUR[2]) is None
        # a block re-placed by a failover is no longer at home
        sched.assignment[3] = 0
        assert sched.at_home(0, 3, FOUR[2]) and not sched.at_home(2, 4, FOUR[2])

