"""Pipelined out-of-core ingest engine (ISSUE 7).

Covers the stage-graph runtime (`ingest.pipeline`): in-order delivery
from out-of-order parallel workers, the documented peak-buffered-chunks
bound, cancellation/close semantics, classified decode faults; shard
discovery and multi-file datasets (`ingest.dataset`, `io.stream_*`
multi-path variants): deterministic order, empty shards, zero-row
groups, mixed sizes, corrupt files; the file-handle leak regression;
and the unfoldable-stream host-spill accounting.
"""

import os
import threading
import time

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import config
from tensorframes_tpu import io as tio
from tensorframes_tpu.frame import TensorFrame
from tensorframes_tpu.graph import builder as dsl
from tensorframes_tpu.ingest import (
    Dataset,
    PipeStage,
    discover_shards,
    pipelined,
    stream_dataset,
)
from tensorframes_tpu.testing import faults as chaos
from tensorframes_tpu.utils import telemetry
from tensorframes_tpu.utils.profiling import reset_stats, stats


def _write_shards(root, sizes, fmt="parquet", blocks=2, seed=0):
    """One shard file per entry of ``sizes``; returns (dir, all rows)."""
    rng = np.random.RandomState(seed)
    parts = []
    ext = "parquet" if fmt == "parquet" else "arrow"
    for i, n in enumerate(sizes):
        x = rng.rand(n).astype(np.float32)
        parts.append(x)
        df = TensorFrame.from_dict(
            {"x": x}, num_blocks=min(blocks, max(1, n))
        )
        p = str(root / f"shard-{i:03d}.{ext}")
        if fmt == "parquet":
            tio.write_parquet(df, p)
        else:
            tio.write_arrow_ipc(df, p)
    return str(root), np.concatenate(parts) if parts else np.zeros(0, "f4")


def _sum_graph():
    df0 = TensorFrame.from_dict({"x": np.arange(2.0, dtype=np.float32)})
    xi = tfs.block(df0, "x", tf_name="x_input")
    return dsl.reduce_sum(xi, axes=[0]).named("x")


def _min_graph():
    df0 = TensorFrame.from_dict({"x": np.arange(2.0, dtype=np.float32)})
    xi = tfs.block(df0, "x", tf_name="x_input")
    return dsl.reduce_min(xi, axes=[0]).named("x")


# ---------------------------------------------------------------------------
# the stage-graph runtime
# ---------------------------------------------------------------------------


class TestPipelineRuntime:
    def test_in_order_delivery_from_out_of_order_workers(self):
        # workers race (staggered sleeps), delivery must re-sequence
        def slow_double(i):
            time.sleep(0.002 * (3 - i % 4))
            return i * 2

        out = list(
            pipelined(
                iter(range(40)),
                [PipeStage("decode", slow_double, workers=4)],
                depth=2,
            )
        )
        assert out == [i * 2 for i in range(40)]

    def test_peak_buffered_chunks_bound(self):
        # The documented bound for the canonical chain
        # discovery -> decode(W) -> transfer with delivery depth d:
        # at most W + 2d + 4 chunks live at once (ingest/pipeline.py).
        W, d = 3, 2
        live = [0]
        peak = [0]
        lock = threading.Lock()

        def decode(i):
            with lock:
                live[0] += 1
                peak[0] = max(peak[0], live[0])
            return i

        def transfer(i):
            return i

        src = iter(range(60))
        it = pipelined(
            src,
            [
                PipeStage("decode", decode, workers=W, cheap_input=True),
                PipeStage("transfer-stage", transfer),
            ],
            depth=d,
        )
        for _ in it:
            with lock:
                live[0] -= 1
            time.sleep(0.002)  # slow consumer: the pipeline runs ahead
        assert peak[0] <= W + 2 * d + 4, peak[0]
        assert peak[0] >= 2  # it DID run ahead (otherwise no pipeline)

    def test_stream_prefetch_depth_config_respected(self):
        # depth=None reads config.stream_prefetch_depth (was the
        # hard-coded depth=1): producer run-ahead is bounded by it
        produced = [0]

        def src():
            for i in range(100):
                produced[0] += 1
                yield i

        with config.override(stream_prefetch_depth=3):
            from tensorframes_tpu.streaming import _prefetch_iter

            it = _prefetch_iter(src())
            assert next(it) == 0
            time.sleep(0.3)  # producer fills the bounded queue and blocks
            # consumed 1 + queue(depth=3) + producer's item in hand + 1
            assert produced[0] <= 1 + 3 + 2, produced[0]
            it.close()

    def test_serial_mode_same_results_no_threads(self):
        def double(i):
            return i * 2

        with config.override(ingest_pipeline=False):
            before = threading.active_count()
            out = list(
                pipelined(
                    iter(range(10)), [PipeStage("decode", double)], depth=2
                )
            )
            assert threading.active_count() == before
        assert out == [i * 2 for i in range(10)]

    def test_serial_mode_stamps_errors(self):
        def src():
            yield 0
            raise RuntimeError("bad shard")

        with config.override(ingest_pipeline=False):
            it = pipelined(src(), [], depth=1)
            assert next(it) == 0
            with pytest.raises(RuntimeError, match="bad shard") as ei:
                next(it)
        assert ei.value.tfs_chunk_index == 1
        assert ei.value.tfs_pipeline_stage == "producer"

    def test_abandon_closes_source_promptly(self):
        closed = threading.Event()

        def src():
            try:
                for i in range(1000):
                    yield i
            finally:
                closed.set()

        it = pipelined(src(), [], depth=1)
        assert next(it) == 0
        it.close()
        assert closed.wait(5.0), "source generator was not closed"

    def test_stage_error_carries_context_and_fails_fast(self):
        attempts = {"n": 0}

        def decode(i):
            if i == 2:
                attempts["n"] += 1
                raise ValueError("corrupt chunk")
            return i

        it = pipelined(
            iter(range(5)),
            [
                PipeStage(
                    "decode",
                    decode,
                    workers=2,
                    context=lambda i: {"tfs_shard_path": f"shard-{i}"},
                )
            ],
            depth=1,
        )
        got = [next(it), next(it)]
        with pytest.raises(ValueError, match="corrupt chunk") as ei:
            list(it)
        assert got == [0, 1]
        assert ei.value.tfs_chunk_index == 2
        assert ei.value.tfs_pipeline_stage == "decode"
        assert ei.value.tfs_shard_path == "shard-2"
        # deterministic => exactly one attempt, no retry burn
        assert attempts["n"] == 1

    def test_non_iterable_source_raises_not_hangs(self):
        # a source whose __iter__ raises must surface on the consumer
        # (the producer thread forwarding it as an error message), not
        # die silently and leave the consumer blocked forever
        with pytest.raises(TypeError) as ei:
            next(pipelined(42, [], depth=1))
        assert ei.value.tfs_pipeline_stage == "producer"

    def test_transient_stage_error_retried_in_place(self):
        failed = {"n": 0}
        lock = threading.Lock()

        def decode(i):
            if i == 3:
                with lock:
                    failed["n"] += 1
                    if failed["n"] == 1:
                        raise RuntimeError("UNAVAILABLE: flaky reader")
            return i * 10

        with config.override(retry_backoff_base_s=0.001):
            out = list(
                pipelined(
                    iter(range(6)),
                    [PipeStage("decode", decode, workers=2)],
                    depth=1,
                )
            )
        assert out == [i * 10 for i in range(6)]
        assert failed["n"] == 2  # failed once, retried once, succeeded


# ---------------------------------------------------------------------------
# shard discovery
# ---------------------------------------------------------------------------


class TestDiscovery:
    def test_directory_sorted_deterministic(self, tmp_path):
        root, _ = _write_shards(tmp_path, [4, 4, 4])
        shards = discover_shards(root)
        assert [os.path.basename(p) for p, f in shards] == [
            "shard-000.parquet", "shard-001.parquet", "shard-002.parquet"
        ]
        assert all(f == "parquet" for _, f in shards)
        assert discover_shards(root) == shards  # rerun: identical

    def test_glob_and_list_mix(self, tmp_path):
        root, _ = _write_shards(tmp_path, [4, 4])
        ipc_root = tmp_path / "ipc"
        ipc_root.mkdir()
        _write_shards(ipc_root, [4], fmt="ipc")
        shards = discover_shards(
            [os.path.join(root, "*.parquet"), str(ipc_root)]
        )
        fmts = [f for _, f in shards]
        assert fmts == ["parquet", "parquet", "ipc"]

    def test_missing_and_empty_are_loud(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            discover_shards(str(tmp_path / "nope.parquet"))
        with pytest.raises(ValueError, match="matched no shards"):
            discover_shards(str(tmp_path / "*.parquet"))
        empty = tmp_path / "empty"
        empty.mkdir()
        with pytest.raises(ValueError, match="no Parquet/IPC shards"):
            discover_shards(str(empty))

    def test_format_inference_and_override(self, tmp_path):
        df = TensorFrame.from_dict({"x": np.arange(3.0)})
        odd = str(tmp_path / "data.bin")
        tio.write_parquet(df, odd)
        with pytest.raises(ValueError, match="cannot infer"):
            discover_shards(odd)
        assert discover_shards(odd, format="parquet") == [(odd, "parquet")]

    def test_tasks_group_metadata(self, tmp_path):
        root, _ = _write_shards(tmp_path, [10, 6], blocks=3)
        ds = Dataset(root, chunk_groups=2)
        tasks = list(ds.tasks())
        # shard 0: 3 row groups -> 2 tasks (2+1); shard 1: 3 -> 2 tasks
        assert [t.shard_index for t in tasks] == [0, 0, 1, 1]
        assert sum(t.rows for t in tasks) == 16
        assert tasks[0].groups == (0, 1)

    def test_ipc_discovery_is_metadata_only(self, tmp_path):
        # IPC footers expose the batch COUNT cheaply but not row counts;
        # discovery must not decode data to learn them (a serial full
        # read on the producer thread is the bottleneck this PR removes)
        root, _ = _write_shards(tmp_path, [9], fmt="ipc", blocks=3)
        tasks = list(Dataset(root).tasks())
        assert len(tasks) == 3
        assert all(t.rows == -1 for t in tasks)  # unknown, by contract


# ---------------------------------------------------------------------------
# multi-file streaming end to end
# ---------------------------------------------------------------------------


class TestStreamDataset:
    def test_mixed_shard_sizes_match_whole_reduce(self, tmp_path):
        root, allx = _write_shards(tmp_path, [37, 5, 120, 1], blocks=4)
        whole = TensorFrame.from_dict({"x": allx})
        want_sum = float(tfs.reduce_blocks(_sum_graph(), whole))
        want_min = float(tfs.reduce_blocks(_min_graph(), whole))
        got_sum = float(
            tfs.reduce_blocks_stream(
                _sum_graph(), stream_dataset(root, decode_workers=3)
            )
        )
        got_min = float(
            tfs.reduce_blocks_stream(
                _min_graph(), stream_dataset(root, decode_workers=3)
            )
        )
        assert got_min == want_min  # bit-identical
        np.testing.assert_allclose(got_sum, want_sum, rtol=1e-6)

    @pytest.mark.parametrize(
        "ingest_pipeline, plan_pipeline",
        [(True, True), (False, True), (False, False)],
        ids=["pipelined", "ingest-serial", "stage-serial"],
    )
    def test_multi_fetch_stream_matches_whole_frame(
        self, tmp_path, ingest_pipeline, plan_pipeline
    ):
        """A multi-shard dataset streamed into a sum/min/max reduce, and
        a lazy per-chunk map fused into a min, give the whole frame's
        answers (min and max bit-identical, the sum within the
        reassociation tolerance) with no host sync, whether the stages
        overlap or run one after another."""
        root, allx = _write_shards(tmp_path, [300, 300, 300, 300], blocks=2)
        df0 = TensorFrame.from_dict({"x": allx[:2]})
        fetches = [
            fn(tfs.block(df0, "x", tf_name=f"{name}_input"), axes=[0])
            .named(name)
            for name, fn in (("s", dsl.reduce_sum), ("mn", dsl.reduce_min),
                             ("mx", dsl.reduce_max))
        ]
        feeds = {"s_input": "x", "mn_input": "x", "mx_input": "x"}
        xi = tfs.block(df0, "x", tf_name="x_input")
        z = (dsl.tanh(xi) * 0.25 + xi).named("z")
        zmin = dsl.reduce_min(
            tfs.block(df0, "x", tf_name="zmn_input"), axes=[0]
        ).named("zmn")
        whole = TensorFrame.from_dict({"x": allx}, num_blocks=4)
        ref = tfs.reduce_blocks(fetches, whole, feed_dict=feeds)
        ref_map = whole.lazy().map_blocks(
            z, feed_dict={"x_input": "x"}
        ).reduce_blocks(zmin, feed_dict={"zmn_input": "z"})
        with config.override(
            ingest_pipeline=ingest_pipeline, plan_pipeline=plan_pipeline
        ):
            tfs.reduce_blocks_stream(  # compiles out of the counted run
                fetches, stream_dataset(root, decode_workers=2), feeds
            )
            reset_stats()
            got = tfs.reduce_blocks_stream(
                fetches, stream_dataset(root, decode_workers=2), feeds
            )
            got = {k: float(np.asarray(v)) for k, v in got.items()}
            syncs = stats().get("host_sync", 0)
            got_map = tfs.reduce_blocks_stream(
                zmin,
                (f.lazy().map_blocks(z, feed_dict={"x_input": "x"})
                 for f in stream_dataset(root, decode_workers=2)),
                feed_dict={"zmn_input": "z"},
            )
        assert syncs == 0
        assert got["mn"] == float(ref["mn"])
        assert got["mx"] == float(ref["mx"])
        np.testing.assert_allclose(got["s"], float(ref["s"]), rtol=1e-5)
        assert float(np.asarray(got_map)) == float(np.asarray(ref_map))

    def test_empty_shard_contributes_nothing(self, tmp_path):
        root, allx = _write_shards(tmp_path, [8, 8])
        empty = TensorFrame.from_dict({"x": np.zeros(0, np.float32)})
        tio.write_parquet(empty, str(tmp_path / "shard-00a.parquet"))
        total = tfs.reduce_blocks_stream(
            _sum_graph(), stream_dataset(root, decode_workers=2)
        )
        np.testing.assert_allclose(
            float(total), allx.sum(dtype=np.float64), rtol=1e-6
        )

    def test_zero_row_record_batch_skipped(self, tmp_path):
        # IPC keeps zero-row batches; the stream must skip them, not
        # dispatch an empty reduce
        df = TensorFrame.from_dict({"x": np.arange(6.0, dtype=np.float32)})
        df.offsets = [0, 3, 3, 6]  # middle block is empty
        p = str(tmp_path / "z.arrow")
        tio.write_arrow_ipc(df, p)
        total = tfs.reduce_blocks_stream(_sum_graph(), stream_dataset(p))
        assert float(total) == 15.0

    def test_io_multi_path_variants_route_to_pipeline(self, tmp_path):
        root, allx = _write_shards(tmp_path, [9, 9])
        from tensorframes_tpu.ingest import IngestStream

        by_dir = tio.stream_parquet(root)
        assert isinstance(by_dir, IngestStream)
        assert sum(f.nrows for f in by_dir) == allx.size
        by_glob = tio.stream_parquet(os.path.join(root, "*.parquet"))
        assert sum(f.nrows for f in by_glob) == allx.size
        (tmp_path / "i").mkdir()
        ipc_root, _ = _write_shards(tmp_path / "i", [7], fmt="ipc")
        by_list = tio.stream_arrow_ipc(
            [os.path.join(ipc_root, "shard-000.arrow")]
        )
        assert sum(f.nrows for f in by_list) == 7

    def test_ingest_stream_is_an_iterator_with_close(self, tmp_path):
        # the multi-path readers must honor the SAME contract as the
        # single-file generators: next() works, close() releases the
        # pipeline (and shard handles), exhaustion is final
        root, allx = _write_shards(tmp_path, [6, 6, 6])
        it = tio.stream_parquet(root)
        first = next(it)
        assert first.nrows > 0
        it.close()  # must not raise; cancels the pipeline
        # a partially-consumed IngestStream degrades to a plain chunk
        # iterator inside reduce_blocks_stream (no pipeline rebuild —
        # the already-consumed chunk stays consumed)
        it2 = stream_dataset(root, decode_workers=2)
        skipped = next(it2)
        rest = float(tfs.reduce_blocks_stream(_sum_graph(), it2))
        want = allx.sum(dtype=np.float64) - np.asarray(
            skipped["x"].host_values()
        ).sum(dtype=np.float64)
        np.testing.assert_allclose(rest, want, rtol=1e-5)

    def test_single_file_keeps_plain_generator(self, tmp_path):
        root, _ = _write_shards(tmp_path, [6])
        it = tio.stream_parquet(os.path.join(root, "shard-000.parquet"))
        from tensorframes_tpu.ingest import IngestStream

        assert not isinstance(it, IngestStream)
        assert sum(f.nrows for f in it) == 6

    def test_corrupt_shard_fails_fast_with_context(self, tmp_path):
        root, _ = _write_shards(tmp_path, [8, 8])
        bad = str(tmp_path / "shard-001x.parquet")
        with open(bad, "wb") as f:
            f.write(b"PAR1 this is not a parquet file")
        with pytest.raises(Exception) as ei:
            tfs.reduce_blocks_stream(
                _sum_graph(), stream_dataset(root, decode_workers=2)
            )
        assert getattr(ei.value, "tfs_shard_path", None) == bad
        assert getattr(ei.value, "tfs_chunk_index", None) is not None

    def test_injected_decode_fault_transient_recovers(self, tmp_path):
        root, allx = _write_shards(tmp_path, [16, 16, 16])
        with config.override(retry_backoff_base_s=0.001):
            with chaos.inject_stage(stage="decode", nth=[1]) as plan:
                total = tfs.reduce_blocks_stream(
                    _sum_graph(), stream_dataset(root, decode_workers=2)
                )
        assert plan.injected == 1
        np.testing.assert_allclose(
            float(total), allx.sum(dtype=np.float64), rtol=1e-6
        )

    def test_injected_decode_fault_deterministic_names_shard(self, tmp_path):
        root, _ = _write_shards(tmp_path, [16, 16, 16])
        with chaos.inject_stage(
            stage="decode", nth=[2], fault="deterministic"
        ) as plan:
            with pytest.raises(chaos.InjectedFault) as ei:
                tfs.reduce_blocks_stream(
                    _sum_graph(), stream_dataset(root, decode_workers=2)
                )
        assert plan.injected == 1
        assert ei.value.tfs_pipeline_stage == "decode"
        assert str(ei.value.tfs_shard_path).endswith(".parquet")
        assert ei.value.tfs_chunk_index is not None


# ---------------------------------------------------------------------------
# file-handle leak regression (ISSUE 7 satellite)
# ---------------------------------------------------------------------------


def _fds_for(path: str):
    out = []
    for fd in os.listdir("/proc/self/fd"):
        try:
            if os.readlink(f"/proc/self/fd/{fd}") == path:
                out.append(fd)
        except OSError:
            continue
    return out


@pytest.mark.skipif(
    not os.path.isdir("/proc/self/fd"), reason="needs /proc fd table"
)
class TestHandleLeak:
    def test_stream_parquet_partial_consumption_closes(self, tmp_path):
        root, _ = _write_shards(tmp_path, [12], blocks=4)
        p = os.path.join(root, "shard-000.parquet")
        it = tio.stream_parquet(p)
        next(it)  # partially consumed
        assert _fds_for(p)  # handle is open mid-stream
        it.close()  # abandon: try/finally must close NOW, not at GC
        assert not _fds_for(p)

    def test_stream_arrow_ipc_partial_consumption_closes(self, tmp_path):
        root, _ = _write_shards(tmp_path, [12], fmt="ipc", blocks=4)
        p = os.path.join(root, "shard-000.arrow")
        it = tio.stream_arrow_ipc(p)
        next(it)
        assert _fds_for(p)
        it.close()
        assert not _fds_for(p)

    def test_abandoned_pipelined_stream_closes_handles(self, tmp_path):
        # the single-file reader on the PIPELINE's producer thread: the
        # runtime must close the source deterministically on abandon
        # (refcount GC is not prompt on another thread)
        root, _ = _write_shards(tmp_path, [40], blocks=8)
        p = os.path.join(root, "shard-000.parquet")
        it = iter(pipelined(tio.stream_parquet(p), [], depth=1))
        next(it)
        it.close()
        deadline = time.time() + 5.0
        while _fds_for(p) and time.time() < deadline:
            time.sleep(0.01)
        assert not _fds_for(p)


# ---------------------------------------------------------------------------
# unfoldable-stream host spill accounting (ISSUE 7 satellite)
# ---------------------------------------------------------------------------


class TestSpillAccounting:
    def test_spill_counts_host_sync_and_d2h_bytes(self):
        # Sum(x*x) streams unfoldably (single final combine), so every
        # chunk past the first spills the previous partial to host —
        # that is a real D2H sync and must be visible to diagnostics
        df0 = TensorFrame.from_dict({"x": np.arange(3.0, dtype=np.float32)})
        xi = tfs.block(df0, "x", tf_name="x_input")
        sq = dsl.reduce_sum(xi * xi, axes=[0]).named("x")
        chunks = [
            TensorFrame.from_dict(
                {"x": np.full(3, float(i), dtype=np.float32)}
            )
            for i in range(4)
        ]
        telemetry.reset()
        reset_stats()
        tfs.reduce_blocks_stream(sq, iter(chunks))
        spills = [
            s for s in telemetry.spans()
            if s.name == "reduce_blocks_stream.spill"
        ]
        assert spills and all(s.kind == "host_sync" for s in spills)
        assert stats().get("host_sync", 0) >= len(spills) >= 2
        _, _, hists = telemetry.metrics_snapshot()
        d2h = [v for (name, _), v in hists.items() if name == "d2h_bytes"]
        assert d2h and d2h[0][3] >= len(spills)  # observation count

    def test_foldable_stream_never_spills(self):
        chunks = [
            TensorFrame.from_dict(
                {"x": np.full(3, float(i), dtype=np.float32)}
            )
            for i in range(4)
        ]
        telemetry.reset()
        reset_stats()
        tfs.reduce_blocks_stream(_sum_graph(), iter(chunks))
        assert stats().get("host_sync", 0) == 0


class TestConfigKnobs:
    def test_defaults(self):
        c = config.Config()
        assert c.stream_prefetch_depth == 1
        assert c.ingest_pipeline is True
        assert c.ingest_decode_workers == 0

    def test_env_seeding(self, monkeypatch):
        monkeypatch.setenv("TFS_STREAM_PREFETCH_DEPTH", "5")
        monkeypatch.setenv("TFS_INGEST_PIPELINE", "0")
        monkeypatch.setenv("TFS_INGEST_DECODE_WORKERS", "7")
        c = config.Config()
        assert c.stream_prefetch_depth == 5
        assert c.ingest_pipeline is False
        assert c.ingest_decode_workers == 7
