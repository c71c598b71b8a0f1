"""TensorFrame tests (mirror ExtraOperationsSuite's analyze coverage)."""

import numpy as np
import pytest

from tensorframes_tpu.frame import Column, TensorFrame
from tensorframes_tpu.schema import ScalarType, Shape


class TestColumn:
    def test_dense_scalar(self):
        c = Column("x", np.arange(5, dtype=np.float64))
        assert c.is_dense
        assert c.cell_shape == Shape(())
        assert c.dtype is ScalarType.float64
        assert len(c) == 5

    def test_dense_vector(self):
        c = Column("x", np.ones((4, 3), dtype=np.float32))
        assert c.cell_shape == Shape((3,))

    def test_ragged_densifies_when_uniform(self):
        c = Column("x", [np.ones(3), np.zeros(3)])
        assert c.is_dense
        assert c.cell_shape == Shape((3,))

    def test_ragged_stays_ragged(self):
        c = Column("x", [np.ones(2), np.zeros(3)])
        assert not c.is_dense
        assert c.cell_shape == Shape((None,))  # rank known, dims not

    def test_ragged_analyze(self):
        c = Column("x", [np.ones(2), np.zeros(3)])
        assert c.analyzed_cell_shape() == Shape((None,))
        c2 = Column("y", [np.ones((2, 4)), np.zeros((3, 4))])
        assert c2.analyzed_cell_shape() == Shape((None, 4))

    def test_rank_mismatch(self):
        with pytest.raises(ValueError):
            Column("x", [np.ones(2), np.zeros((2, 2))])

    def test_string_column(self):
        c = Column("s", ["ab", "cde"])
        assert c.dtype is ScalarType.string

    def test_bulk_path_never_aliases_caller_memory(self):
        # the bulk np.asarray fast path is list/tuple-only precisely so
        # zero-copy array-likes (a pandas Series shares its buffer) go
        # through the copying per-cell path
        pd = pytest.importorskip("pandas")
        s = pd.Series([1.0, 2.0, 3.0])
        c = Column("x", s)
        s.iloc[0] = 99.0
        assert float(c.values[0]) == 1.0
        assert not np.shares_memory(c.values, s.to_numpy())

    def test_generator_input_consumed_once(self):
        c = Column("x", (np.array([i, i + 1.0]) for i in range(3)))
        assert c.is_dense and c.values.shape == (3, 2)

    def test_bulk_path_dtype_coercion(self):
        c = Column("x", [1, 2, 3], ScalarType.int32)
        assert c.values.dtype == np.int32
        c2 = Column("x", [1.5, 2.5])
        assert c2.dtype is ScalarType.float64


class TestTensorFrame:
    def test_from_dict_blocks(self):
        tf = TensorFrame.from_dict({"x": np.arange(10.0)}, num_blocks=3)
        assert tf.nrows == 10
        assert tf.num_blocks == 3
        assert sum(tf.block_sizes()) == 10
        # blocks cover the rows exactly
        rows = np.concatenate([b["x"].values for b in tf.blocks()])
        np.testing.assert_array_equal(rows, np.arange(10.0))

    def test_uneven_blocks(self):
        # the reference tests explicit uneven partitions
        # (BasicOperationsSuite.scala:219-227)
        tf = TensorFrame.from_dict({"x": np.arange(5.0)}, num_blocks=3)
        assert tf.num_blocks == 3
        assert sum(tf.block_sizes()) == 5

    def test_column_mismatch(self):
        with pytest.raises(ValueError):
            TensorFrame([Column("a", np.ones(2)), Column("b", np.ones(3))])

    def test_analyze_refines_shape(self):
        tf = TensorFrame.from_dict({"x": [np.ones(3), 2 * np.ones(3), np.zeros(4)]})
        assert tf.info["x"].cell_shape == Shape((None,))
        tf2 = tf.analyze()
        assert tf2.info["x"].cell_shape == Shape((None,))
        tf3 = TensorFrame.from_dict({"x": [np.ones((2, 5)), np.ones((3, 5))]})
        assert tf3.analyze().info["x"].cell_shape == Shape((None, 5))

    def test_append_shape(self):
        tf = TensorFrame.from_dict({"x": [np.ones(3), np.ones(3), np.ones(4)]})
        tf2 = tf.append_shape("x", Shape((None,)))
        assert tf2.info["x"].cell_shape == Shape((None,))

    def test_pandas_roundtrip(self):
        import pandas as pd

        pdf = pd.DataFrame({"x": [1.0, 2.0], "y": [[1.0, 2.0], [3.0, 4.0]]})
        tf = TensorFrame.from_pandas(pdf)
        assert tf.info["x"].cell_shape == Shape(())
        assert tf.info["y"].cell_shape == Shape((2,))
        back = tf.to_pandas()
        assert list(back["x"]) == [1.0, 2.0]
        assert back["y"][0] == [1.0, 2.0]

    def test_collect(self):
        tf = TensorFrame.from_dict({"x": np.arange(3.0)})
        rows = tf.collect()
        assert len(rows) == 3
        assert rows[1]["x"] == 1.0

    def test_select_and_with_columns(self):
        tf = TensorFrame.from_dict({"a": np.ones(4), "b": np.zeros(4)})
        assert tf.select(["b"]).columns == ["b"]
        tf2 = tf.with_columns([Column("c", np.full(4, 7.0))])
        assert set(tf2.columns) == {"a", "b", "c"}

    def test_from_rows(self):
        tf = TensorFrame.from_rows([{"x": 1.0}, {"x": 2.0}])
        assert tf.nrows == 2

    def test_rows_to_device_and_back(self):
        """Rows in, a device column, a verb, rows out: the row <-> tensor
        round trip of the reference's conversion suites."""
        import tensorframes_tpu as tfs

        n = 1000
        dev = TensorFrame.from_rows([{"x": i} for i in range(n)]).to_device()
        out = tfs.map_blocks(lambda x: {"y": x + x}, dev)
        rows = out.collect()
        assert len(rows) == n
        assert [int(r["y"]) for r in rows] == [2 * i for i in range(n)]
        vec = TensorFrame.from_rows([{"v": np.arange(n, dtype=np.int64)}])
        np.testing.assert_array_equal(
            np.asarray(vec.to_device()["v"].values), [np.arange(n)]
        )


class TestArrowInterop:
    def test_roundtrip(self):
        import pyarrow as pa

        tf = TensorFrame.from_dict(
            {
                "x": np.arange(4.0),
                "v": np.arange(8.0).reshape(4, 2),
                "r": [np.arange(1.0), np.arange(2.0), np.arange(3.0), np.arange(1.0)],
            }
        )
        table = tf.to_arrow()
        assert isinstance(table, pa.Table)
        back = TensorFrame.from_arrow(table)
        np.testing.assert_array_equal(back["x"].values, tf["x"].values)
        np.testing.assert_array_equal(back["v"].values, tf["v"].values)
        assert not back["r"].is_dense
        np.testing.assert_array_equal(back["r"].row(2), [0.0, 1.0, 2.0])

    def test_from_arrow_primitive(self):
        import pyarrow as pa

        t = pa.table({"a": pa.array([1, 2, 3], pa.int64())})
        tf = TensorFrame.from_arrow(t, num_blocks=2)
        assert tf.num_blocks == 2
        np.testing.assert_array_equal(tf["a"].values, [1, 2, 3])


class TestPadRagged:
    def test_pad_and_lengths(self):
        tf = TensorFrame.from_dict(
            {"v": [np.arange(2.0), np.arange(4.0), np.arange(1.0)]}
        )
        padded = tf.pad_ragged("v")
        assert padded["v"].is_dense
        assert padded["v"].values.shape == (3, 4)
        np.testing.assert_array_equal(padded["v_len"].values, [2, 4, 1])
        np.testing.assert_array_equal(padded["v"].values[0], [0, 1, 0, 0])

    def test_masked_block_op_over_padded(self):
        # the intended use: masked mean per row over the padded block
        import tensorframes_tpu as tfs

        tf = TensorFrame.from_dict(
            {"v": [np.arange(2.0) + 1, np.arange(4.0) + 1]}
        ).pad_ragged("v")
        out = tfs.map_blocks(
            lambda v, v_len: {"m": v.sum(axis=1) / v_len}, tf
        )
        np.testing.assert_allclose(out["m"].values, [1.5, 2.5])

    def test_dense_noop(self):
        tf = TensorFrame.from_dict({"v": np.ones((3, 2))})
        assert tf.pad_ragged("v") is tf


class TestBlockToRow:
    def test_equal_blocks_densify(self):
        import tensorframes_tpu as tfs

        tf = TensorFrame.from_dict(
            {"x": np.arange(6.0), "v": np.arange(12.0).reshape(6, 2)},
            num_blocks=2,
        )
        out = tfs.block_to_row(tf)
        assert len(out["x"]) == 2
        assert out["x"].values.shape == (2, 3)
        assert out["v"].values.shape == (2, 3, 2)
        np.testing.assert_array_equal(out["x"].values[1], [3.0, 4.0, 5.0])

    def test_unequal_blocks_ragged(self):
        import tensorframes_tpu as tfs

        tf = TensorFrame.from_dict({"x": np.arange(5.0)}, num_blocks=2)
        out = tfs.block_to_row(tf)
        assert not out["x"].is_dense
        sizes = sorted(len(c) for c in out["x"].ragged)
        assert sizes == [2, 3]

    def test_ragged_input_rejected(self):
        import tensorframes_tpu as tfs

        tf = TensorFrame.from_dict({"v": [np.arange(2.0), np.arange(3.0)]})
        with pytest.raises(ValueError, match="ragged"):
            tfs.block_to_row(tf)


class TestExplainDetailed:
    def test_returns_frame_info(self):
        import tensorframes_tpu as tfs

        tf = TensorFrame.from_dict({"x": np.arange(3.0)})
        info = tfs.explain_detailed(tf)
        assert info.names == ["x"]
        assert info["x"].dtype is ScalarType.float64


class TestParquet:
    """Parquet ingest/egress: row groups map to blocks the way IPC
    record batches do; `stream_parquet` feeds reduce_blocks_stream in
    bounded memory."""

    def test_roundtrip_preserves_blocks(self, tmp_path):
        from tensorframes_tpu import io as tio

        df = TensorFrame.from_dict(
            {
                "x": np.arange(10.0),
                "v": np.arange(20.0).reshape(10, 2),
            },
            num_blocks=3,
        )
        p = str(tmp_path / "t.parquet")
        tio.write_parquet(df, p)
        back = tio.read_parquet(p)
        np.testing.assert_array_equal(back["x"].values, df["x"].values)
        np.testing.assert_array_equal(back["v"].values, df["v"].values)
        assert back.offsets == df.offsets

    def test_stream_reduce(self, tmp_path):
        import tensorframes_tpu as tfs
        from tensorframes_tpu import dsl
        from tensorframes_tpu import io as tio

        df = TensorFrame.from_dict({"x": np.arange(100.0)}, num_blocks=4)
        p = str(tmp_path / "s.parquet")
        tio.write_parquet(df, p)
        s = dsl.reduce_sum(
            tfs.block(df, "x", tf_name="x_input"), axes=[0]
        ).named("x")
        total = tfs.reduce_blocks_stream(s, tio.stream_parquet(p))
        assert float(total) == np.arange(100.0).sum()

    def test_repartition_on_read(self, tmp_path):
        from tensorframes_tpu import io as tio

        df = TensorFrame.from_dict({"x": np.arange(12.0)}, num_blocks=3)
        p = str(tmp_path / "r.parquet")
        tio.write_parquet(df, p)
        back = tio.read_parquet(p, num_blocks=6)
        assert back.num_blocks == 6
        np.testing.assert_array_equal(back["x"].values, df["x"].values)

    def test_string_column_roundtrip(self, tmp_path):
        from tensorframes_tpu import io as tio

        df = TensorFrame.from_dict(
            {"k": np.array(["a", "bb", "c"], dtype=object), "x": np.arange(3.0)}
        )
        p = str(tmp_path / "str.parquet")
        tio.write_parquet(df, p)
        back = tio.read_parquet(p)
        assert [str(v) for v in back["k"].host_values()] == ["a", "bb", "c"]

    def test_block_larger_than_default_row_group(self, tmp_path):
        # code-review r4: pyarrow splits writes at its 1Mi-row default
        # row-group size; the writer must pin row_group_size per block
        # or a >1Mi-row block comes back as several blocks.
        from tensorframes_tpu import io as tio

        df = TensorFrame.from_dict(
            {"x": np.zeros(1_500_000, dtype=np.float32)}
        )
        p = str(tmp_path / "big.parquet")
        tio.write_parquet(df, p)
        back = tio.read_parquet(p)
        assert back.num_blocks == 1
        assert back.nrows == 1_500_000


class TestArrowIPC:
    """Arrow IPC file ingest/egress (`tensorframes_tpu.io`): blocks map
    to record batches both directions; the streaming reader feeds
    reduce_blocks_stream in bounded memory."""

    def test_roundtrip_preserves_blocks(self, tmp_path):
        from tensorframes_tpu import io as tio

        df = TensorFrame.from_dict(
            {
                "x": np.arange(10.0),
                "v": np.arange(20.0).reshape(10, 2),
            },
            num_blocks=3,
        )
        p = str(tmp_path / "t.arrow")
        tio.write_arrow_ipc(df, p)
        back = tio.read_arrow_ipc(p)
        np.testing.assert_array_equal(back.column("x").values, df.column("x").values)
        np.testing.assert_array_equal(back.column("v").values, df.column("v").values)
        assert back.offsets == df.offsets

    def test_empty_blocks_preserved(self, tmp_path):
        # empty blocks become zero-row record batches and survive the
        # round trip (round-1 advisor finding: they were silently dropped)
        from tensorframes_tpu import io as tio

        df = TensorFrame.from_dict({"x": np.arange(6.0)})
        df.offsets = [0, 3, 3, 6]
        p = str(tmp_path / "e.arrow")
        tio.write_arrow_ipc(df, p)
        back = tio.read_arrow_ipc(p)
        assert back.offsets == [0, 3, 3, 6]
        np.testing.assert_array_equal(back.column("x").values, df.column("x").values)

    def test_all_empty_frame_roundtrip(self, tmp_path):
        from tensorframes_tpu import io as tio

        df = TensorFrame.from_dict({"x": np.zeros((0,), dtype=np.float32)})
        p = str(tmp_path / "z.arrow")
        tio.write_arrow_ipc(df, p)
        back = tio.read_arrow_ipc(p)
        assert back.nrows == 0
        assert back.column("x").values.dtype == np.float32

    def test_ragged_roundtrip(self, tmp_path):
        from tensorframes_tpu import io as tio

        df = TensorFrame.from_dict(
            {"r": [np.arange(i + 1.0) for i in range(5)]}
        )
        p = str(tmp_path / "r.arrow")
        tio.write_arrow_ipc(df, p)
        back = tio.read_arrow_ipc(p)
        for got, want in zip(back.column("r").rows(), df.column("r").rows()):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_stream_reduce_matches_eager(self, tmp_path):
        from tensorframes_tpu import dsl
        from tensorframes_tpu import io as tio

        data = np.arange(100.0)
        df = TensorFrame.from_dict({"x": data}, num_blocks=10)
        p = str(tmp_path / "s.arrow")
        tio.write_arrow_ipc(df, p)

        frames = tio.stream_arrow_ipc(p, batches_per_frame=3)
        first = TensorFrame.from_dict({"x": data[:1]})
        import tensorframes_tpu as tfs
        x_input = tfs.block(first, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        total = tfs.reduce_blocks_stream(s, frames)
        assert float(total) == float(data.sum())

    def test_stream_is_lazy(self, tmp_path):
        from tensorframes_tpu import io as tio

        df = TensorFrame.from_dict({"x": np.arange(12.0)}, num_blocks=4)
        p = str(tmp_path / "l.arrow")
        tio.write_arrow_ipc(df, p)
        it = tio.stream_arrow_ipc(p)
        chunk = next(it)
        assert chunk.nrows == 3  # one record batch per frame
        assert sum(f.nrows for f in it) == 9
