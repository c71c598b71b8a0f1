"""The five verbs — integration tests through the public API.

Mirrors the reference's `BasicOperationsSuite` (identity/add/reduce across
ranks 0-2, multiple uneven partitions), `TrimmingOperationsSuite`
(row-count-changing maps), and `core_test.py` (feed_dict renames, groupby,
map/reduce round-trips)."""

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import dsl
from tensorframes_tpu.schema import ScalarType, Shape


def frame_of(**cols):
    return tfs.TensorFrame.from_dict(cols)


class TestMapBlocks:
    def test_readme_x_plus_3(self):
        # The README flagship example.
        df = tfs.TensorFrame.from_dict({"x": np.array([1.0, 2.0, 3.0])})
        x = tfs.block(df, "x")
        z = (x + 3.0).named("z")
        out = tfs.map_blocks(z, df)
        assert out.columns == ["z", "x"]  # TF cols first, then passthrough
        np.testing.assert_array_equal(out["z"].values, [4.0, 5.0, 6.0])
        np.testing.assert_array_equal(out["x"].values, [1.0, 2.0, 3.0])

    def test_identity_rank1(self):
        df = frame_of(x=np.ones((4, 3)))
        x = tfs.block(df, "x")
        y = dsl.identity(x).named("y")
        out = tfs.map_blocks(y, df)
        np.testing.assert_array_equal(out["y"].values, np.ones((4, 3)))

    def test_multiple_blocks_uneven(self):
        # BasicOperationsSuite.scala:219-227 (explicit uneven partitions).
        df = tfs.TensorFrame.from_dict({"x": np.arange(7.0)}, num_blocks=3)
        x = tfs.block(df, "x")
        out = tfs.map_blocks((x * 2.0).named("y"), df)
        np.testing.assert_array_equal(out["y"].values, 2 * np.arange(7.0))
        assert out.num_blocks == 3

    def test_block_reduction_inside_map(self):
        # A graph may reduce over the block dim (k-means pattern): each
        # block sees its own lead dim, like each Spark partition did.
        df = tfs.TensorFrame.from_dict({"x": np.arange(6.0)}, num_blocks=2)
        x = tfs.block(df, "x")
        s = dsl.reduce_sum(x, axes=[0], keep_dims=True)
        centered = (x - s / 3.0).named("c")  # block mean with 3 rows/block
        out = tfs.map_blocks(centered, df)
        np.testing.assert_array_equal(
            out["c"].values, np.array([-1, 0, 1, -1, 0, 1.0])
        )

    def test_feed_dict_rename(self):
        # core_test.py feed renames: placeholder name != column name.
        df = frame_of(y=np.array([1.0, 2.0]))
        x = dsl.placeholder(ScalarType.float64, Shape((None,)), name="x")
        out = tfs.map_blocks((x + 1.0).named("z"), df, feed_dict={"x": "y"})
        np.testing.assert_array_equal(out["z"].values, [2.0, 3.0])

    def test_trimmed_map(self):
        # TrimmingOperationsSuite: row count may change; inputs dropped.
        df = frame_of(x=np.arange(6.0))
        x = tfs.block(df, "x")
        s = dsl.reduce_sum(x, axes=[0], keep_dims=True).named("s")
        out = tfs.map_blocks(s, df, trim=True)
        assert out.columns == ["s"]
        assert out.nrows == 1
        np.testing.assert_array_equal(out["s"].values, [15.0])

    def test_missing_trim_raises(self):
        df = frame_of(x=np.arange(6.0))
        x = tfs.block(df, "x")
        s = dsl.reduce_sum(x, axes=[0], keep_dims=True).named("s")
        with pytest.raises(ValueError, match="trim"):
            tfs.map_blocks(s, df)

    def test_dtype_mismatch(self):
        df = frame_of(x=np.arange(3, dtype=np.int32))
        ph = dsl.placeholder(ScalarType.float64, Shape((None,)), name="x")
        with pytest.raises(ValueError, match="dtype"):
            tfs.map_blocks((ph + 1.0).named("z"), df)

    def test_missing_column(self):
        df = frame_of(x=np.arange(3.0))
        ph = dsl.placeholder(ScalarType.float64, Shape((None,)), name="nope")
        with pytest.raises(ValueError, match="not in the frame"):
            tfs.map_blocks((ph + 1.0).named("z"), df)

    def test_shape_incompat(self):
        df = frame_of(x=np.ones((3, 2)))
        ph = dsl.placeholder(ScalarType.float64, Shape((None, 5)), name="x")
        with pytest.raises(ValueError, match="not compatible"):
            tfs.map_blocks((ph + 1.0).named("z"), df)

    def test_two_outputs_sorted(self):
        df = frame_of(x=np.arange(3.0))
        x = tfs.block(df, "x")
        b = (x + 1.0).named("b")
        a = (x * 2.0).named("a")
        out = tfs.map_blocks([b, a], df)
        assert out.columns == ["a", "b", "x"]

    def test_function_frontend(self):
        # TPU-native path: a plain function over column arrays.
        df = frame_of(x=np.arange(4.0), y=np.ones(4))
        out = tfs.map_blocks(lambda x, y: {"z": x * y + 1.0}, df)
        np.testing.assert_array_equal(out["z"].values, np.arange(4.0) + 1.0)
        assert out.columns == ["z", "x", "y"]

    def test_vector_block(self):
        df = frame_of(v=np.arange(12.0).reshape(4, 3))
        v = tfs.block(df, "v")
        out = tfs.map_blocks((v * 2.0).named("w"), df)
        np.testing.assert_array_equal(out["w"].values, 2 * df["v"].values)


class TestMapRows:
    def test_scalar_rows(self):
        df = frame_of(x=np.arange(4.0))
        x = tfs.row(df, "x")
        out = tfs.map_rows((x + 1.0).named("y"), df)
        np.testing.assert_array_equal(out["y"].values, np.arange(4.0) + 1)

    def test_vector_rows_vmapped(self):
        df = frame_of(v=np.arange(8.0).reshape(4, 2))
        v = tfs.row(df, "v")
        s = dsl.reduce_sum(v, axes=[0]).named("s")
        out = tfs.map_rows(s, df)
        np.testing.assert_array_equal(out["s"].values, df["v"].values.sum(1))

    def test_ragged_rows(self):
        # variable-length vectors per row (TFDataOps.scala:90-103)
        df = tfs.TensorFrame.from_dict({"v": [np.arange(2.0), np.arange(5.0)]})
        v = tfs.row(df, "v")
        s = dsl.reduce_sum(v, axes=[0]).named("s")
        out = tfs.map_rows(s, df)
        np.testing.assert_array_equal(out["s"].values, [1.0, 10.0])

    def test_ragged_output_column(self):
        df = tfs.TensorFrame.from_dict({"v": [np.arange(2.0), np.arange(3.0)]})
        v = tfs.row(df, "v")
        out = tfs.map_rows((v * 2.0).named("w"), df)
        assert not out["w"].is_dense
        np.testing.assert_array_equal(out["w"].row(1), [0.0, 2.0, 4.0])

    def test_function_frontend(self):
        df = frame_of(x=np.arange(4.0))
        out = tfs.map_rows(lambda x: {"y": x * x}, df)
        np.testing.assert_array_equal(out["y"].values, np.arange(4.0) ** 2)


class TestRaggedMapRowsBucketed:
    """Ragged map_rows runs shape-bucketed: rows grouped by cell shape,
    one vmapped XLA call per (shape, pow2-padded bucket) — the SURVEY §7
    shape-bucketing plan, replacing the round-1 per-row dispatch loop."""

    def _ragged(self, n, shapes=((2,), (5,), (3,))):
        rng = np.random.default_rng(0)
        return tfs.TensorFrame.from_dict(
            {"v": [rng.normal(size=shapes[i % len(shapes)]) for i in range(n)]}
        )

    def test_matches_per_row_semantics(self):
        df = self._ragged(50)
        v = tfs.row(df, "v")
        s = dsl.reduce_sum(v, axes=[0]).named("s")
        out = tfs.map_rows(s, df)
        want = [float(np.sum(np.asarray(df["v"].row(i)))) for i in range(50)]
        np.testing.assert_allclose(out["s"].values, want)

    def test_row_order_preserved_in_ragged_output(self):
        df = self._ragged(17)
        v = tfs.row(df, "v")
        out = tfs.map_rows((v * 2.0).named("w"), df)
        for i in range(17):
            np.testing.assert_allclose(
                np.asarray(out["w"].row(i)), np.asarray(df["v"].row(i)) * 2.0
            )

    def test_compile_count_bounded(self):
        from tensorframes_tpu.runtime.executor import Executor

        # 4 distinct cell shapes over 1000 rows with uneven bucket sizes:
        # compiles must scale with shapes x log(bucket), not rows
        rng = np.random.default_rng(1)
        lens = [1 + (i * i) % 4 for i in range(1000)]
        df = tfs.TensorFrame.from_dict(
            {"v": [rng.normal(size=(l,)) for l in lens]}
        )
        v = tfs.row(df, "v")
        s = dsl.reduce_sum(v, axes=[0]).named("s")
        ex = Executor()
        tfs.map_rows(s, df, executor=ex)
        (vfn,) = ex._cache.values()
        # 4 shapes x at most a few pow2 bucket paddings
        assert vfn._cache_size() <= 8, vfn._cache_size()

    def test_fn_frontend_ragged(self):
        df = self._ragged(23)
        out = tfs.map_rows(lambda v: {"m": v.max()}, df)
        want = [float(np.asarray(df["v"].row(i)).max()) for i in range(23)]
        np.testing.assert_allclose(out["m"].values, want)


class TestReduceBlocks:
    def test_vector_sum(self):
        # README vector reduce_sum — the BASELINE north-star config.
        df = tfs.TensorFrame.from_dict({"x": np.arange(10.0)}, num_blocks=3)
        x_input = tfs.block(df, "x", tf_name="x_input")
        x = dsl.reduce_sum(x_input, axes=[0]).named("x")
        res = tfs.reduce_blocks(x, df)
        assert float(res) == 45.0

    def test_reduce_min(self):
        df = tfs.TensorFrame.from_dict({"x": np.array([5.0, 2.0, 9.0])}, num_blocks=2)
        x_input = tfs.block(df, "x", tf_name="x_input")
        x = dsl.reduce_min(x_input, axes=[0]).named("x")
        assert float(tfs.reduce_blocks(x, df)) == 2.0

    def test_vector_cell_sum(self):
        df = tfs.TensorFrame.from_dict(
            {"v": np.arange(12.0).reshape(6, 2)}, num_blocks=3
        )
        v_input = tfs.block(df, "v", tf_name="v_input")
        v = dsl.reduce_sum(v_input, axes=[0]).named("v")
        res = tfs.reduce_blocks(v, df)
        np.testing.assert_array_equal(res, df["v"].values.sum(0))

    def test_multi_output(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(4.0), "y": np.ones(4)})
        x_input = tfs.block(df, "x", tf_name="x_input")
        y_input = tfs.block(df, "y", tf_name="y_input")
        x = dsl.reduce_sum(x_input, axes=[0]).named("x")
        y = dsl.reduce_sum(y_input, axes=[0]).named("y")
        res = tfs.reduce_blocks([x, y], df)
        assert res["x"] == 6.0 and res["y"] == 4.0

    def test_int64_map_then_sum_exact(self):
        # the reference's performance suite: x + x over longs, then a sum
        n = 100_000
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(n, dtype=np.int64)}, num_blocks=4
        ).to_device()
        mapped = tfs.map_blocks((tfs.block(df, "x") * 2).named("z"), df)
        s = dsl.reduce_sum(
            tfs.block(mapped, "z", tf_name="z_input"), axes=[0]
        ).named("z")
        assert int(tfs.reduce_blocks(s, mapped)) == (n - 1) * n

    def test_mean_variance_in_one_two_fetch_pass(self):
        # squares by map_blocks, then [sum, sum of squares] from ONE
        # reduce pass: the associative form of mean and variance
        data = np.random.RandomState(0).rand(4000, 8).astype(np.float32)
        df = tfs.TensorFrame.from_dict({"v": data}, num_blocks=8)
        squared = tfs.map_blocks(
            dsl.square(tfs.block(df, "v")).named("vsq"), df
        )
        s = dsl.reduce_sum(
            tfs.block(squared, "v", tf_name="v_input"), axes=[0]
        ).named("v")
        sq = dsl.reduce_sum(
            tfs.block(squared, "vsq", tf_name="vsq_input"), axes=[0]
        ).named("vsq")
        res = tfs.reduce_blocks([s, sq], squared)
        mean = np.asarray(res["v"]) / len(data)
        var = np.asarray(res["vsq"]) / len(data) - mean**2
        np.testing.assert_allclose(mean, data.mean(0), rtol=1e-5)
        np.testing.assert_allclose(var, data.var(0), rtol=1e-3)

    def test_naming_convention_enforced(self):
        df = frame_of(x=np.arange(3.0))
        bad = tfs.block(df, "x", tf_name="wrong")  # must be named 'x_input'
        s = dsl.reduce_sum(bad, axes=[0]).named("x")
        with pytest.raises(ValueError, match="x_input"):
            tfs.reduce_blocks(s, df, feed_dict={"wrong": "x"})


class TestReduceRows:
    def test_pairwise_sum(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(5.0)}, num_blocks=2)
        x1 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_1")
        x2 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_2")
        x = dsl.add(x1, x2).named("x")
        assert float(tfs.reduce_rows(x, df)) == 10.0

    def test_single_row_frame(self):
        df = frame_of(x=np.array([7.0]))
        x1 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_1")
        x2 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_2")
        assert float(tfs.reduce_rows(dsl.add(x1, x2).named("x"), df)) == 7.0

    def test_vector_cells(self):
        df = frame_of(v=np.arange(6.0).reshape(3, 2))
        v1 = dsl.placeholder(ScalarType.float64, Shape((2,)), name="v_1")
        v2 = dsl.placeholder(ScalarType.float64, Shape((2,)), name="v_2")
        res = tfs.reduce_rows(dsl.add(v1, v2).named("v"), df)
        np.testing.assert_array_equal(res, df["v"].values.sum(0))

    def test_left_fold_order(self):
        # Non-associative graph: fold order must match the reference's
        # sequential per-partition fold (single block -> exact order).
        df = frame_of(x=np.array([8.0, 4.0, 2.0]))
        x1 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_1")
        x2 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_2")
        res = tfs.reduce_rows(dsl.div(x1, x2).named("x"), df)
        assert float(res) == (8.0 / 4.0) / 2.0

    def test_convention_enforced(self):
        df = frame_of(x=np.arange(3.0))
        x1 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_1")
        bad = dsl.placeholder(ScalarType.float64, Shape(()), name="other")
        with pytest.raises(ValueError, match="convention"):
            tfs.reduce_rows(dsl.add(x1, bad).named("x"), df)


class TestAggregate:
    def test_grouped_sum(self):
        # core_test.py:255-264 groupby test shape.
        df = tfs.TensorFrame.from_dict(
            {
                "key": np.array([1, 1, 2, 2, 2], dtype=np.int64),
                "x": np.array([1.0, 2.0, 10.0, 20.0, 30.0]),
            }
        )
        x_input = tfs.block(df, "x", tf_name="x_input")
        x = dsl.reduce_sum(x_input, axes=[0]).named("x")
        out = tfs.aggregate(x, tfs.group_by(df, "key"))
        assert set(out.columns) == {"key", "x"}
        got = dict(zip(out["key"].values.tolist(), out["x"].values.tolist()))
        assert got == {1: 3.0, 2: 60.0}

    def test_string_group_keys(self):
        # The reference grouped by ANY Catalyst column type; string keys
        # are the common case from Arrow/Spark ingest (pyarrow string
        # columns arrive as object dtype, which never densifies).
        df = tfs.TensorFrame.from_dict(
            {
                "k": np.array(["a", "b", "a", "c"], dtype=object),
                "x": np.arange(4.0),
            }
        )
        x_input = tfs.block(df, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        out = tfs.aggregate(s, tfs.group_by(df, "k"))
        got = dict(
            zip(
                [str(v) for v in out["k"].host_values()],
                out["x"].values.tolist(),
            )
        )
        assert got == {"a": 2.0, "b": 1.0, "c": 3.0}
        pdf = out.to_pandas().sort_values("k")
        assert pdf["x"].tolist() == [2.0, 1.0, 3.0]

    def test_onehot_and_scatter_segment_plans_agree(self):
        # The MXU one-hot matmul lowering (num_keys <=
        # config.aggregate_onehot_keys) must agree with the scatter-add
        # segment_sum lowering up to FP reassociation, for Sum and Mean.
        from tensorframes_tpu import config as tfs_config

        rng = np.random.RandomState(3)
        keys = rng.randint(0, 37, 5000).astype(np.int64)
        vals = rng.rand(5000, 3)
        df = tfs.TensorFrame.from_dict({"k": keys, "v": vals})
        vi = tfs.block(df, "v", tf_name="v_input")
        for make_s in (
            lambda: dsl.reduce_sum(vi, axes=[0]).named("v"),
            lambda: dsl.reduce_mean(vi, axes=[0]).named("v"),
        ):
            # forced on (the auto default only engages on TPU backends)
            with tfs_config.override(aggregate_onehot_keys=256):
                out_oh = tfs.aggregate(make_s(), tfs.group_by(df, "k"))
            with tfs_config.override(aggregate_onehot_keys=0):
                out_sc = tfs.aggregate(make_s(), tfs.group_by(df, "k"))
            np.testing.assert_allclose(
                np.asarray(out_oh["v"].values),
                np.asarray(out_sc["v"].values),
                rtol=1e-10,
            )

    def test_empty_string_keyed_aggregate(self):
        # code-review r4: a 0-row string-keyed aggregate (empty
        # Spark/Arrow partition) must return an empty frame like the
        # numeric case — in aggregate_global a crash here would kill
        # one process before its collectives and hang the others.
        from tensorframes_tpu.schema import ScalarType

        df0 = tfs.TensorFrame.from_dict(
            {"k": np.array([], dtype=object), "x": np.zeros(0)},
            dtypes={"k": ScalarType.string},
        )
        probe = tfs.TensorFrame.from_dict({"x": np.zeros(4)})
        x_input = tfs.block(probe, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        out = tfs.aggregate(s, tfs.group_by(df0, "k"))
        assert out.nrows == 0
        assert set(out.columns) == {"k", "x"}

    def test_mixed_dtype_multi_key(self):
        df = tfs.TensorFrame.from_dict(
            {
                "a": np.array(["p", "q", "p"], dtype=object),
                "b": np.array([1, 1, 2], dtype=np.int64),
                "x": np.arange(3.0),
            }
        )
        x_input = tfs.block(df, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        out = tfs.aggregate(s, tfs.group_by(df, "a", "b"))
        pdf = out.to_pandas().sort_values(["a", "b"]).reset_index(drop=True)
        assert [tuple(r) for r in pdf.to_numpy()] == [
            ("p", 1, 0.0), ("p", 2, 2.0), ("q", 1, 1.0),
        ]

    def test_grouped_vector_mean_two_outputs(self):
        df = tfs.TensorFrame.from_dict(
            {
                "k": np.array([0, 1, 0, 1], dtype=np.int64),
                "v": np.arange(8.0).reshape(4, 2),
                "cnt": np.ones(4),
            }
        )
        v_input = tfs.block(df, "v", tf_name="v_input")
        c_input = tfs.block(df, "cnt", tf_name="cnt_input")
        v = dsl.reduce_sum(v_input, axes=[0]).named("v")
        cnt = dsl.reduce_sum(c_input, axes=[0]).named("cnt")
        out = tfs.aggregate([v, cnt], tfs.group_by(df, "k"))
        k0 = np.nonzero(out["k"].values == 0)[0][0]
        np.testing.assert_array_equal(out["v"].values[k0], [4.0, 6.0])
        assert out["cnt"].values[k0] == 2.0

    def test_uneven_group_sizes(self):
        rng = np.random.RandomState(0)
        keys = rng.randint(0, 5, size=50).astype(np.int64)
        vals = rng.rand(50)
        df = tfs.TensorFrame.from_dict({"key": keys, "x": vals})
        x_input = tfs.block(df, "x", tf_name="x_input")
        x = dsl.reduce_sum(x_input, axes=[0]).named("x")
        out = tfs.aggregate(x, tfs.group_by(df, "key"))
        for k, s in zip(out["key"].values, out["x"].values):
            np.testing.assert_allclose(s, vals[keys == k].sum(), rtol=1e-12)

    def test_non_scalar_key_rejected(self):
        df = frame_of(k=np.ones((3, 2)), x=np.arange(3.0))
        with pytest.raises(ValueError, match="scalar"):
            tfs.group_by(df, "k")


class TestSchemaVerbs:
    def test_analyze_print_append(self, capsys):
        df = tfs.TensorFrame.from_dict({"v": [np.ones(3), np.zeros(3)]})
        df2 = tfs.analyze(df)
        assert df2.info["v"].cell_shape == Shape((3,))
        tfs.print_schema(df2)
        assert "v: float64" in capsys.readouterr().out
        df3 = tfs.append_shape(df, "v", [None])
        assert df3.info["v"].cell_shape == Shape((None,))

    def test_explain(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(3.0)})
        assert "x: float64" in tfs.explain(df)


class TestGraphDefImport:
    def test_map_blocks_from_graphdef_bytes(self):
        # Export a DSL graph to wire bytes, re-import, execute: the
        # reference's GraphDef interchange path (graphFromFile,
        # PythonInterface.scala:115-118).
        df = tfs.TensorFrame.from_dict({"x": np.arange(4.0)})
        x = tfs.block(df, "x")
        z = (x + 3.0).named("z")
        g, fetch_names = dsl.build(z)
        out = tfs.map_blocks(g.to_bytes(), df, fetch_names=fetch_names)
        np.testing.assert_array_equal(out["z"].values, np.arange(4.0) + 3.0)

    def test_import_requires_fetches(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(4.0)})
        g, _ = dsl.build((tfs.block(df, "x") + 1.0).named("z"))
        with pytest.raises(ValueError, match="fetch_names"):
            tfs.map_blocks(g.to_bytes(), df)


class TestReviewRegressions:
    """Regressions from code review: suffix-convention hijacking, fold
    mapping consistency, fn-front-end trim validation, compile caching."""

    def test_literal_column_name_wins_over_suffix(self):
        # A column literally named 'temp_1' must not be re-routed to 'temp'.
        df = frame_of(temp=np.zeros(3), temp_1=np.array([0.0, 1.0, 2.0]))
        ph = tfs.block(df, "temp_1")
        out = tfs.map_blocks((ph + 1.0).named("z"), df)
        np.testing.assert_array_equal(out["z"].values, [1.0, 2.0, 3.0])

    def test_reduce_rows_mapping_mismatch_rejected(self):
        df = frame_of(a=np.arange(3.0), b=np.arange(3.0))
        from tensorframes_tpu.schema import ScalarType, Shape

        x1 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_1")
        x2 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_2")
        with pytest.raises(ValueError, match="same column"):
            tfs.reduce_rows(
                dsl.add(x1, x2).named("x"),
                df,
                feed_dict={"x_1": "a", "x_2": "b"},
            )

    def test_fn_trim_scalar_output_clear_error(self):
        df = frame_of(x=np.arange(4.0))
        with pytest.raises(ValueError, match="lead"):
            tfs.map_blocks(lambda x: {"s": x.sum()}, df, trim=True)

    def test_fn_trim_disagreeing_outputs(self):
        df = frame_of(x=np.arange(4.0))
        with pytest.raises(ValueError, match="disagree"):
            tfs.map_blocks(
                lambda x: {"a": x[:2], "b": x}, df, trim=True
            )

    def test_executor_cache_reused_across_calls(self):
        ex = tfs.Executor()
        df = frame_of(x=np.arange(4.0))
        x = tfs.block(df, "x")
        z = (x + 1.0).named("z")
        g, fetches = dsl.build(z)
        from tensorframes_tpu.graph.ir import Graph

        g2 = Graph.from_bytes(g.to_bytes())
        tfs.map_blocks(g, df, fetch_names=fetches, executor=ex)
        n = ex.compile_count
        tfs.map_blocks(g2, df, fetch_names=fetches, executor=ex)
        assert ex.compile_count == n  # same fingerprint -> cache hit

    def test_map_rows_executor_cached(self):
        ex = tfs.Executor()
        df = frame_of(x=np.arange(4.0))
        from tensorframes_tpu.schema import ScalarType, Shape

        ph = dsl.placeholder(ScalarType.float64, Shape(()), name="x")
        g, fetches = dsl.build((ph * 2.0).named("y"))
        tfs.map_rows(g, df, fetch_names=fetches, executor=ex)
        n = ex.compile_count
        tfs.map_rows(g, df, fetch_names=fetches, executor=ex)
        assert ex.compile_count == n

    def test_executor_cache_lru_bounded(self):
        # code-review r4: the compile cache must not grow without bound
        # in a long-lived process whose graphs drift. Hot entries
        # survive eviction (LRU), cold ones are dropped and recompile.
        from tensorframes_tpu import config as tfs_config

        ex = tfs.Executor()
        df = frame_of(x=np.arange(4.0))
        x = tfs.block(df, "x")
        graphs = [dsl.build((x + float(i)).named("z")) for i in range(5)]
        with tfs_config.override(executor_cache_entries=3):
            for g, fetches in graphs[:3]:
                tfs.map_blocks(g, df, fetch_names=fetches, executor=ex)
            assert len(ex._cache) == 3
            # touch graph 0 so it is most-recent, then insert two more:
            # graphs 1 and 2 evict, graph 0 survives
            tfs.map_blocks(
                graphs[0][0], df, fetch_names=graphs[0][1], executor=ex
            )
            for g, fetches in graphs[3:]:
                tfs.map_blocks(g, df, fetch_names=fetches, executor=ex)
            assert len(ex._cache) == 3
            n = ex.compile_count
            tfs.map_blocks(
                graphs[0][0], df, fetch_names=graphs[0][1], executor=ex
            )
            assert ex.compile_count == n  # survived as most-recent
            tfs.map_blocks(
                graphs[1][0], df, fetch_names=graphs[1][1], executor=ex
            )
            assert ex.compile_count == n + 1  # evicted: recompiles


class TestReduceBlocksStream:
    def test_streamed_chunks_match(self):
        chunks = [
            tfs.TensorFrame.from_dict({"x": np.arange(i * 10.0, (i + 1) * 10.0)})
            for i in range(5)
        ]
        x_input = tfs.block(chunks[0], "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        total = tfs.reduce_blocks_stream(s, iter(chunks))
        assert float(total) == np.arange(50.0).sum()

    def test_single_chunk(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(4.0)})
        x_input = tfs.block(df, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        assert float(tfs.reduce_blocks_stream(s, [df])) == 6.0

    def test_empty_iterator(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(4.0)})
        x_input = tfs.block(df, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        with pytest.raises(ValueError, match="empty"):
            tfs.reduce_blocks_stream(s, [])

    def test_partials_tree_folded_bounded(self, monkeypatch):
        # The partial table must stay O(fold_every) on the host no matter
        # how long the stream: every combine call sees a stacked frame of
        # lead dim <= fold_every (round-2 weakness: partials grew
        # O(#chunks) before one final combine).
        from tensorframes_tpu import api as _api

        leads = []
        real_reduce_blocks = _api.reduce_blocks

        def spy(graph, frame, feed_dict=None, **kw):
            leads.append(frame.nrows)
            return real_reduce_blocks(graph, frame, feed_dict, **kw)

        monkeypatch.setattr(_api, "reduce_blocks", spy)
        # 5-row chunks so combine calls (over partials, <= fold_every=4
        # rows) are distinguishable from chunk calls (5 rows)
        chunks = [
            tfs.TensorFrame.from_dict({"x": np.arange(i * 5.0, i * 5.0 + 5)})
            for i in range(11)
        ]
        x_input = tfs.block(chunks[0], "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        total = tfs.reduce_blocks_stream(s, iter(chunks), fold_every=4)
        assert float(total) == np.arange(55.0).sum()
        folds = [n for n in leads if n != 5]
        # 11 chunks, fold_every=4: folds at chunks 4/8, then 1 fold + 3
        # tail chunks combine at the end — never more than 4 partials live
        assert len(folds) >= 2
        assert max(folds) <= 4

    def test_auto_fold_engages_for_sum(self, monkeypatch):
        # Default fold policy: associative monoid fetches (Sum) are
        # tree-folded without the caller passing fold_every.
        from tensorframes_tpu import api as _api

        leads = []
        real_reduce_blocks = _api.reduce_blocks

        def spy(graph, frame, feed_dict=None, **kw):
            leads.append(frame.nrows)
            return real_reduce_blocks(graph, frame, feed_dict, **kw)

        monkeypatch.setattr(_api, "reduce_blocks", spy)
        chunks = [
            tfs.TensorFrame.from_dict({"x": np.full(3, float(i))})
            for i in range(70)
        ]
        x_input = tfs.block(chunks[0], "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        total = tfs.reduce_blocks_stream(s, iter(chunks))
        assert float(total) == 3 * sum(range(70))
        # 70 chunks with the auto cadence of 64: at least one fold call
        # over the partial table (lead = 64) before the final combine
        assert 64 in leads

    def test_auto_fold_disabled_for_mean(self, monkeypatch):
        # ADVICE r3: Mean partials re-entering a fold weighted as one
        # chunk would skew the result once the stream exceeds the fold
        # cadence. The auto policy must keep ALL chunk partials for a
        # single equally-weighted final combine — exact for equal-sized
        # chunks, like the reference's pairwise combine contract.
        from tensorframes_tpu import api as _api

        leads = []
        real_reduce_blocks = _api.reduce_blocks

        def spy(graph, frame, feed_dict=None, **kw):
            leads.append(frame.nrows)
            return real_reduce_blocks(graph, frame, feed_dict, **kw)

        monkeypatch.setattr(_api, "reduce_blocks", spy)
        n_chunks = 70  # > the 64-chunk auto cadence
        chunks = [
            tfs.TensorFrame.from_dict({"x": np.full(3, float(i))})
            for i in range(n_chunks)
        ]
        x_input = tfs.block(chunks[0], "x", tf_name="x_input")
        m = dsl.reduce_mean(x_input, axes=[0]).named("x")
        total = tfs.reduce_blocks_stream(m, iter(chunks))
        # exact: mean of per-chunk means over equal-sized chunks
        assert float(total) == pytest.approx(np.mean(range(n_chunks)))
        # no intermediate fold: the only non-3-row call is the final
        # combine over all 70 partials
        folds = [n for n in leads if n != 3]
        assert folds == [n_chunks]

    def test_auto_fold_disabled_for_transform_then_reduce(self, monkeypatch):
        # code-review r4: Sum(x*x) classifies as a "sum" monoid for the
        # chunk plan, but stream partials recombine through the SAME
        # graph — a fold would square the partial sums. The auto gate
        # must require the reduce to consume its placeholder directly.
        from tensorframes_tpu import api as _api

        leads = []
        real_reduce_blocks = _api.reduce_blocks

        def spy(graph, frame, feed_dict=None, **kw):
            leads.append(frame.nrows)
            return real_reduce_blocks(graph, frame, feed_dict, **kw)

        monkeypatch.setattr(_api, "reduce_blocks", spy)
        n_chunks = 70
        chunks = [
            tfs.TensorFrame.from_dict({"x": np.full(3, float(i))})
            for i in range(n_chunks)
        ]
        x_input = tfs.block(chunks[0], "x", tf_name="x_input")
        sq = dsl.reduce_sum((x_input * x_input), axes=[0]).named("x")
        total = tfs.reduce_blocks_stream(sq, iter(chunks))
        folds = [n for n in leads if n != 3]
        assert folds == [n_chunks]  # single final combine, no tree fold
        # (the final combine still re-squares partials — that is the
        # documented same-graph combine contract, unchanged from the
        # reference's reducePairBlock; what matters is folding never
        # compounds it)
        # chunk i partial = sum(i^2 over 3 rows) = 3i^2; the final
        # same-graph combine computes sum((3i^2)^2)
        assert float(total) == float(
            np.sum(np.array([3 * i * i for i in range(n_chunks)], float) ** 2)
        )


class TestBindings:
    """Per-call bound placeholders: jit arguments, not baked constants."""

    def test_map_rows_bindings(self):
        from tensorframes_tpu.runtime.executor import default_executor

        p = dsl.placeholder(ScalarType.float64, Shape(()), name="v")
        w = dsl.placeholder(ScalarType.float64, Shape(()), name="w")
        df = frame_of(v=np.arange(4.0))
        y = (p * w).named("y")
        out = tfs.map_rows(y, df, bindings={"w": np.float64(10.0)})
        np.testing.assert_array_equal(out["y"].values, np.arange(4.0) * 10)
        # rebinding reuses the compiled executable
        n = default_executor().compile_count
        out2 = tfs.map_rows(y, df, bindings={"w": np.float64(-1.0)})
        assert default_executor().compile_count == n
        np.testing.assert_array_equal(out2["y"].values, np.arange(4.0) * -1)

    def test_map_rows_fn_front_end_bindings(self):
        df = frame_of(v=np.arange(4.0))
        out = tfs.map_rows(
            lambda v, w: {"y": v * w}, df, bindings={"w": np.float64(7.0)}
        )
        np.testing.assert_array_equal(out["y"].values, np.arange(4.0) * 7)

    def test_map_rows_all_bound_rejected(self):
        df = frame_of(v=np.arange(4.0))
        w = dsl.placeholder(ScalarType.float64, Shape(()), name="w")
        with pytest.raises(ValueError, match="every placeholder is bound"):
            tfs.map_rows(
                (w * 2.0).named("y"), df, bindings={"w": np.float64(1.0)}
            )

    def test_map_rows_bindings_ragged_rejected(self):
        p = dsl.placeholder(ScalarType.float64, Shape((None,)), name="v")
        w = dsl.placeholder(ScalarType.float64, Shape(()), name="w")
        df = tfs.TensorFrame.from_dict(
            {"v": [np.arange(2.0), np.arange(3.0)]}
        )
        with pytest.raises(ValueError, match="ragged"):
            tfs.map_rows(
                dsl.reduce_sum(p * w, axes=[0]).named("y"),
                df,
                bindings={"w": np.float64(2.0)},
            )

    def test_dsl_graph_binding(self):
        df = frame_of(x=np.array([1.0, 2.0, 3.0]))
        x = tfs.block(df, "x")
        w = dsl.placeholder(ScalarType.float64, Shape(()), name="w")
        z = (x * w).named("z")
        out = tfs.map_blocks(z, df, bindings={"w": np.float64(10.0)})
        np.testing.assert_array_equal(out["z"].values, [10.0, 20.0, 30.0])
        # updated binding, same graph object: no rebuild needed
        out2 = tfs.map_blocks(z, df, bindings={"w": np.float64(-1.0)})
        np.testing.assert_array_equal(out2["z"].values, [-1.0, -2.0, -3.0])

    def test_fn_frontend_binding(self):
        df = frame_of(x=np.array([1.0, 2.0]))
        out = tfs.map_blocks(
            lambda x, scale: {"z": x * scale},
            df,
            bindings={"scale": np.float64(3.0)},
        )
        np.testing.assert_array_equal(out["z"].values, [3.0, 6.0])

    def test_vector_binding_multi_block(self):
        df = tfs.TensorFrame.from_dict(
            {"v": np.arange(8.0).reshape(4, 2)}, num_blocks=2
        )
        vv = tfs.block(df, "v")
        c = dsl.placeholder(ScalarType.float64, Shape((2,)), name="offset")
        z = (vv + c).named("z")
        out = tfs.map_blocks(z, df, bindings={"offset": np.array([10.0, 20.0])})
        np.testing.assert_array_equal(out["z"].values[0], [10.0, 21.0])
        np.testing.assert_array_equal(out["z"].values[3], [16.0, 27.0])

    def test_unknown_binding_rejected(self):
        df = frame_of(x=np.array([1.0]))
        x = tfs.block(df, "x")
        z = (x + 1.0).named("z")
        with pytest.raises(ValueError, match="does not match any placeholder"):
            tfs.map_blocks(z, df, bindings={"nope": np.float64(1.0)})

    def test_binding_dtype_mismatch(self):
        df = frame_of(x=np.array([1.0]))
        x = tfs.block(df, "x")
        w = dsl.placeholder(ScalarType.float64, Shape(()), name="w")
        z = (x * w).named("z")
        with pytest.raises(ValueError, match="dtype"):
            tfs.map_blocks(z, df, bindings={"w": np.int32(2)})

    def test_binding_shape_incompatible(self):
        df = frame_of(x=np.arange(4.0).reshape(2, 2))
        x = tfs.block(df, "x")
        c = dsl.placeholder(ScalarType.float64, Shape((2,)), name="c")
        z = (x + c).named("z")
        with pytest.raises(ValueError, match="not compatible"):
            tfs.map_blocks(z, df, bindings={"c": np.zeros((3,))})

    def test_fn_frontend_unknown_binding_rejected(self):
        df = frame_of(x=np.array([1.0]))
        with pytest.raises(ValueError, match="do not match any function"):
            tfs.map_blocks(
                lambda x: {"z": x}, df, bindings={"Scale": np.float64(1.0)}
            )


class TestEmptyBlocks:
    """Empty blocks inside a frame contribute nothing and never reach the
    compiled graph — the reference flags this as an untested TODO
    (`DebugRowOps.scala:386-387`, `:496`, `:520`); here it is pinned."""

    def _frame(self):
        from tensorframes_tpu.frame import Column, TensorFrame

        # blocks: [], [0,1,2], [], [3,4], []
        return TensorFrame(
            [Column("x", np.arange(5.0))], offsets=[0, 0, 3, 3, 5, 5]
        )

    def test_map_blocks_skips_empty(self):
        df = self._frame()
        z = (tfs.block(df, "x") + 3.0).named("z")
        out = tfs.map_blocks(z, df)
        np.testing.assert_allclose(
            out.column("z").values, np.arange(5.0) + 3.0
        )
        assert out.offsets == df.offsets

    def test_map_rows_skips_empty(self):
        df = self._frame()
        z = (tfs.row(df, "x") * 2.0).named("z")
        out = tfs.map_rows(z, df)
        np.testing.assert_allclose(out.column("z").values, np.arange(5.0) * 2)

    def test_reduce_blocks_skips_empty(self):
        df = self._frame()
        x_input = tfs.block(df, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        assert float(tfs.reduce_blocks(s, df)) == 10.0

    def test_reduce_rows_skips_empty(self):
        df = self._frame()
        x1 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_1")
        x2 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_2")
        s = (x1 + x2).named("x")
        assert float(tfs.reduce_rows(s, df)) == 10.0

    def test_trimmed_map_skips_empty(self):
        df = self._frame()
        x = tfs.block(df, "x")
        z = dsl.reduce_sum(x, axes=[0], keep_dims=True).named("z")
        out = tfs.map_blocks(z, df, trim=True)
        np.testing.assert_allclose(out.column("z").values, [3.0, 7.0])

    def test_all_blocks_empty(self):
        from tensorframes_tpu.frame import Column, TensorFrame

        df = TensorFrame(
            [Column("x", np.zeros((0,)))], offsets=[0, 0, 0]
        )
        z = (tfs.block(df, "x") + 3.0).named("z")
        out = tfs.map_blocks(z, df)
        assert out.nrows == 0


class TestAllEmptyFrames:
    """All-empty frames through every verb: the reference's standing TODO
    (`DebugRowOps.scala:386-387,496,520`) closed rather than inherited.
    Graph outputs keep their analyzed dtype/shape even with zero rows."""

    def _empty(self, dtype=np.float64, cell=()):
        from tensorframes_tpu.frame import Column, TensorFrame

        return TensorFrame(
            [Column("x", np.zeros((0,) + cell, dtype=dtype))], offsets=[0, 0]
        )

    def test_map_blocks_unknown_out_dim(self):
        # the round-1 crash: empty-output fallback hit np.zeros(Unknown)
        df = self._empty(cell=(3,))
        x = tfs.block(df, "x")
        z = (x * 2.0).named("z")
        out = tfs.map_blocks(z, df)
        assert out.nrows == 0
        assert out.column("z").values.shape == (0, 3)
        assert out.column("z").values.dtype == np.float64

    def test_map_blocks_dtype_preserved(self):
        df = self._empty(dtype=np.int32)
        z = (tfs.block(df, "x") + np.int32(1)).named("z")
        out = tfs.map_blocks(z, df)
        assert out.column("z").values.dtype == np.int32

    def test_map_blocks_trim(self):
        df = self._empty()
        x = tfs.block(df, "x")
        z = dsl.reduce_sum(x, axes=[0], keep_dims=True).named("z")
        out = tfs.map_blocks(z, df, trim=True)
        assert out.nrows == 0

    def test_map_blocks_fn(self):
        df = self._empty(dtype=np.float32, cell=(2,))
        out = tfs.map_blocks(lambda x: {"z": x * 2}, df)
        assert out.column("z").values.shape == (0, 2)
        assert out.column("z").values.dtype == np.float32

    def test_map_rows(self):
        df = self._empty(cell=(4,))
        z = (tfs.row(df, "x") * 2.0).named("z")
        out = tfs.map_rows(z, df)
        assert out.nrows == 0
        assert out.column("z").values.shape == (0, 4)

    def test_map_rows_fn(self):
        df = self._empty(dtype=np.float32)
        out = tfs.map_rows(lambda x: {"z": x + 1}, df)
        assert out.column("z").values.shape == (0,)
        assert out.column("z").values.dtype == np.float32

    def test_map_blocks_fn_trim_empty(self):
        # a trimmed reduction traced on a zero-row block reports lead 1
        # (keepdims); the empty fallback must still yield zero rows
        df = self._empty(cell=(2,))
        out = tfs.map_blocks(
            lambda x: {"z": x.sum(axis=0, keepdims=True)}, df, trim=True
        )
        assert out.nrows == 0
        assert out.column("z").values.shape == (0, 2)

    def test_map_rows_fn_ragged_empty(self):
        from tensorframes_tpu.frame import Column, TensorFrame

        df = TensorFrame(
            [Column("x", [], dtype=ScalarType.float64)], offsets=[0, 0]
        )
        out = tfs.map_rows(lambda x: {"z": x + 1}, df)
        assert out.column("z").values.shape == (0,)

    def test_reduce_blocks_raises(self):
        df = self._empty()
        x_input = tfs.block(df, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        with pytest.raises(ValueError, match="empty"):
            tfs.reduce_blocks(s, df)

    def test_reduce_rows_raises(self):
        df = self._empty()
        x1 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_1")
        x2 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_2")
        s = (x1 + x2).named("x")
        with pytest.raises(ValueError, match="empty"):
            tfs.reduce_rows(s, df)

    def test_aggregate_empty(self):
        from tensorframes_tpu.frame import Column, TensorFrame

        df = TensorFrame(
            [
                Column("k", np.zeros((0,), dtype=np.int64)),
                Column("x", np.zeros((0,))),
            ],
            offsets=[0, 0],
        )
        s = dsl.reduce_sum(
            tfs.block(df, "x", tf_name="x_input"), axes=[0]
        ).named("x")
        out = tfs.aggregate(s, tfs.group_by(df, "k"))
        assert out.nrows == 0
        assert out.column("x").values.dtype == np.float64

    def test_mesh_map_blocks_empty(self):
        from tensorframes_tpu.parallel import data_mesh

        df = self._empty(dtype=np.float32, cell=(2,))
        z = (tfs.block(df, "x") * 2.0).named("z")
        out = tfs.map_blocks(z, df, mesh=data_mesh())
        assert out.nrows == 0
        assert out.column("z").values.shape == (0, 2)


class TestBytesCells:
    """Bytes/string cells through the map verbs — the reference's Binary
    scope: one scalar cell per row, identity pass-through, never computed
    on (`datatypes.scala:577-581`)."""

    def _frame(self):
        from tensorframes_tpu.frame import Column, TensorFrame

        return TensorFrame(
            [
                Column("tag", [b"a", b"bb", b"ccc"], ScalarType.string),
                Column("x", np.arange(3.0)),
            ]
        )

    def test_map_blocks_passthrough_with_compute(self):
        df = self._frame()
        tag = dsl.placeholder(ScalarType.string, Shape(()), name="tag")
        t = dsl.identity(tag).named("t")
        z = (tfs.block(df, "x") + 1.0).named("z")
        out = tfs.map_blocks([z, t], df)
        assert list(out["t"].rows()) == [b"a", b"bb", b"ccc"]
        np.testing.assert_array_equal(out["z"].values, np.arange(3.0) + 1.0)
        # TF outputs first, sorted; then passthrough inputs
        assert out.columns == ["t", "z", "tag", "x"]

    def test_map_rows_passthrough_only(self):
        df = self._frame()
        tag = dsl.placeholder(ScalarType.string, Shape(()), name="tag")
        out = tfs.map_rows(dsl.identity(tag).named("t"), df)
        assert list(out["t"].rows()) == [b"a", b"bb", b"ccc"]

    def test_passthrough_only_rejects_unknown_bindings(self):
        # pure string pass-through runs no compute graph: a typo'd
        # binding key must raise, not be silently dropped (round-4
        # advisor finding)
        df = self._frame()
        tag = dsl.placeholder(ScalarType.string, Shape(()), name="tag")
        for verb in (tfs.map_rows, tfs.map_blocks):
            with pytest.raises(ValueError, match="typo"):
                verb(
                    dsl.identity(tag).named("t"), df,
                    bindings={"typo": np.float32(5.0)},
                )

    def test_compute_on_bytes_rejected(self):
        from tensorframes_tpu.graph.ir import Graph, GraphNode
        from tensorframes_tpu.proto.graphdef import AttrValue

        # Concat(tag, tag): computes ON the bytes column -> must raise
        g = Graph(
            [
                GraphNode(
                    "tag",
                    "Placeholder",
                    [],
                    {
                        "dtype": AttrValue.of_type(ScalarType.string),
                        "shape": AttrValue.of_shape(Shape(())),
                    },
                ),
                GraphNode("t", "StringJoin", ["tag", "tag"], {}),
            ]
        )
        with pytest.raises(ValueError, match="bytes"):
            tfs.map_blocks(g, self._frame(), fetch_names=["t"])

    def test_feed_dict_rename(self):
        df = self._frame()
        b = dsl.placeholder(ScalarType.string, Shape(()), name="blob")
        out = tfs.map_rows(
            dsl.identity(b).named("t"), df, feed_dict={"blob": "tag"}
        )
        assert list(out["t"].rows()) == [b"a", b"bb", b"ccc"]

    def test_mesh_map_blocks_with_bytes(self):
        # bytes split off BEFORE the mesh dispatch: numeric part shards,
        # bytes cells ride host-side, same result as the local path
        from tensorframes_tpu.frame import Column, TensorFrame
        from tensorframes_tpu.parallel import data_mesh

        df = TensorFrame(
            [
                Column(
                    "tag",
                    [f"r{i}".encode() for i in range(16)],
                    ScalarType.string,
                ),
                Column("x", np.arange(16.0)),
            ]
        )
        tag = dsl.placeholder(ScalarType.string, Shape(()), name="tag")
        z = (tfs.block(df, "x") + 1.0).named("z")
        out = tfs.map_blocks(
            [z, dsl.identity(tag).named("t")], df, mesh=data_mesh()
        )
        assert [bytes(np.asarray(r)[()]) for r in out["t"].rows()] == [
            f"r{i}".encode() for i in range(16)
        ]
        np.testing.assert_array_equal(out["z"].values, np.arange(16.0) + 1.0)


class TestAggregateChunked:
    """Pow2 chunk decomposition for pathological group-size distributions:
    compiles stay O(log max_size) where round 1 compiled one program per
    distinct size (api.py round-1 weakness #4)."""

    def _frame(self, sizes, seed=0):
        rng = np.random.default_rng(seed)
        keys = np.repeat(np.arange(len(sizes), dtype=np.int64), sizes)
        x = rng.normal(size=keys.shape[0])
        return frame_of(k=keys, x=x)

    def _sum_graph(self, df):
        return dsl.reduce_sum(
            tfs.block(df, "x", tf_name="x_input"), axes=[0]
        ).named("x")

    def test_matches_exact_path(self):
        from tensorframes_tpu import config

        sizes = [1, 2, 3, 5, 8, 13, 21, 1, 7]
        df = self._frame(sizes)
        s = self._sum_graph(df)
        exact = tfs.aggregate(s, tfs.group_by(df, "k")).to_pandas()
        with config.override(aggregate_exact_size_limit=1, aggregate_segment_fast=False):
            chunked = tfs.aggregate(s, tfs.group_by(df, "k")).to_pandas()
        exact = exact.sort_values("k").reset_index(drop=True)
        chunked = chunked.sort_values("k").reset_index(drop=True)
        np.testing.assert_allclose(chunked["x"], exact["x"], rtol=1e-12)

    def test_min_graph_chunked(self):
        from tensorframes_tpu import config

        sizes = [3, 1, 4, 1, 5, 9, 2, 6]
        df = self._frame(sizes)
        m = dsl.reduce_min(
            tfs.block(df, "x", tf_name="x_input"), axes=[0]
        ).named("x")
        with config.override(aggregate_exact_size_limit=1, aggregate_segment_fast=False):
            out = tfs.aggregate(m, tfs.group_by(df, "k")).to_pandas()
        out = out.sort_values("k").reset_index(drop=True)
        k = df["k"].values
        x = df["x"].values
        want = [x[k == g].min() for g in range(len(sizes))]
        np.testing.assert_allclose(out["x"], want)

    def test_transform_then_reduce_chunked_exact(self):
        # Sum(x_input * x_input) reduces a TRANSFORM of its rows: the
        # chunk stage applies the transform per row, and the combine uses
        # the DERIVED monoid (add), so chunking stays exact
        from tensorframes_tpu import config

        df = self._frame([3, 5, 7, 2])
        x_input = tfs.block(df, "x", tf_name="x_input")
        ssq = dsl.reduce_sum(x_input * x_input, axes=[0]).named("x")
        with config.override(aggregate_exact_size_limit=1, aggregate_segment_fast=False):
            out = tfs.aggregate(ssq, tfs.group_by(df, "k")).to_pandas()
        out = out.sort_values("k").reset_index(drop=True)
        k = df["k"].values
        x = df["x"].values
        want = [(x[k == g] ** 2).sum() for g in range(4)]
        np.testing.assert_allclose(out["x"], want, rtol=1e-12)

    def test_mean_chunked_size_weighted(self):
        # Mean partials combine size-weighted: a naive partial re-feed
        # would average unequal chunks equally and be silently wrong
        from tensorframes_tpu import config

        sizes = [3, 5, 6, 7, 1]  # non-pow2 sizes force multi-chunk groups
        df = self._frame(sizes)
        x_input = tfs.block(df, "x", tf_name="x_input")
        m = dsl.reduce_mean(x_input, axes=[0]).named("x")
        with config.override(aggregate_exact_size_limit=1, aggregate_segment_fast=False):
            out = tfs.aggregate(m, tfs.group_by(df, "k")).to_pandas()
        out = out.sort_values("k").reset_index(drop=True)
        k = df["k"].values
        x = df["x"].values
        want = [x[k == g].mean() for g in range(len(sizes))]
        np.testing.assert_allclose(out["x"], want, rtol=1e-12)

    def test_integer_mean_uses_exact_plan(self):
        # integer Mean truncates per chunk, so the classifier refuses it
        # and the exact plan computes TF's truncated whole-group mean
        from tensorframes_tpu import config

        keys = np.array([0, 0, 0, 1, 1], dtype=np.int64)
        vals = np.array([0, 1, 5, 7, 2], dtype=np.int64)
        df = frame_of(k=keys, x=vals)
        x_input = tfs.block(df, "x", tf_name="x_input")
        m = dsl.reduce_mean(x_input, axes=[0]).named("x")
        with config.override(aggregate_exact_size_limit=0, aggregate_segment_fast=False):
            out = tfs.aggregate(m, tfs.group_by(df, "k")).to_pandas()
        out = out.sort_values("k").reset_index(drop=True)
        assert out["x"].tolist() == [2, 4]  # 6//3, 9//2 — not 1.67/4.5

    def test_unclassifiable_graph_uses_exact_plan(self):
        # fetch = Min(x) - but wrapped so the root is not a recognized
        # reduce: falls back to the exact whole-group plan (correct,
        # never silently chunk-combined)
        from tensorframes_tpu import config

        df = self._frame([3, 5])
        x_input = tfs.block(df, "x", tf_name="x_input")
        wrapped = dsl.identity(
            dsl.reduce_min(x_input, axes=[0])
        ).named("x")
        with config.override(aggregate_exact_size_limit=1, aggregate_segment_fast=False):
            out = tfs.aggregate(wrapped, tfs.group_by(df, "k")).to_pandas()
        out = out.sort_values("k").reset_index(drop=True)
        k = df["k"].values
        x = df["x"].values
        np.testing.assert_allclose(
            out["x"], [x[k == g].min() for g in range(2)]
        )

    def test_segment_fast_path_engages_by_default(self):
        # Default-on regression pin: a classifiable sum graph must take
        # the sort-free segment path (one "segagg-" compile, no
        # "vmap-agg"), or the 10M-row performance win silently vanishes.
        from tensorframes_tpu.runtime.executor import Executor

        df = self._frame([3, 5, 2])
        s = self._sum_graph(df)
        ex = Executor()
        out = tfs.aggregate(s, tfs.group_by(df, "k"), executor=ex)
        kinds = [k[0] for k in ex._cache]
        assert any(k.startswith("segagg-") for k in kinds), kinds
        assert "vmap-agg" not in kinds
        k = df["k"].values
        x = df["x"].values
        got = dict(
            zip(
                np.asarray(out["k"].values).tolist(),
                np.asarray(out["x"].values).tolist(),
            )
        )
        for g in range(3):
            np.testing.assert_allclose(got[g], x[k == g].sum(), rtol=1e-12)

    def test_lead_rank_constant_rejected_by_classifier(self):
        # A constant shaped (size, *cell) broadcasts along the GROUP-SIZE
        # axis: chunked feeds slice that axis, so the chunk stage would
        # die with an XLA broadcast error. The classifier must refuse it
        # (clean exact-plan fallback) rather than rely on upstream probes
        # to have caught the size-specialization.
        from tensorframes_tpu.api import _chunk_combiners
        from tensorframes_tpu.graph.analysis import NodeSummary
        from tensorframes_tpu.graph.analysis import GraphSummary

        def graph_with_const(cvals):
            x_input = dsl.placeholder(
                ScalarType.float64, Shape((None,)), name="x_input"
            )
            w = dsl.constant(np.asarray(cvals))
            s = dsl.reduce_sum(x_input * w, axes=[0]).named("x")
            g, fl = dsl.build(s)
            summary = GraphSummary(
                inputs={
                    "x_input": NodeSummary(
                        "x_input", True, False,
                        ScalarType.float64, Shape((None,)),
                    )
                },
                outputs={
                    "x": NodeSummary(
                        "x", False, True, ScalarType.float64, Shape(())
                    )
                },
            )
            return g, fl, summary

        # lead-rank (5,) constant against a rank-1 feed: refused
        g, fl, summary = graph_with_const(np.arange(1.0, 6.0))
        assert _chunk_combiners(g, fl, summary) is None
        # scalar constant: chunk-invariant, accepted
        g, fl, summary = graph_with_const(2.0)
        assert _chunk_combiners(g, fl, summary) == {"x": "sum"}

    def test_sub_lead_constant_still_chunks(self):
        # A scalar (sub-lead-rank) constant is chunk-invariant: the
        # classifier must keep accepting it (regression guard for the
        # lead-rank rejection not over-reaching).
        from tensorframes_tpu import config
        from tensorframes_tpu.runtime.executor import Executor

        sizes = np.arange(1, 101)
        df = self._frame(sizes)
        x_input = tfs.block(df, "x", tf_name="x_input")
        s = dsl.reduce_sum(x_input * dsl.constant(2.0), axes=[0]).named("x")
        ex = Executor()
        out = tfs.aggregate(
            s, tfs.group_by(df, "k"), executor=ex
        ).to_pandas()
        (vraw,) = ex._cache.values()
        assert vraw._cache_size() <= 20  # chunked, not one-per-size
        out = out.sort_values("k").reset_index(drop=True)
        k = df["k"].values
        x = df["x"].values
        want = [2.0 * x[k == g].sum() for g in range(len(sizes))]
        np.testing.assert_allclose(out["x"], want, rtol=1e-12)

    def test_compile_count_bounded_many_distinct_sizes(self):
        from tensorframes_tpu.runtime.executor import Executor

        # 400 groups, every size distinct (1..400): the exact plan would
        # compile 400 programs; the chunked plan must stay ~O(log 400)
        sizes = np.arange(1, 401)
        df = self._frame(sizes)
        s = self._sum_graph(df)
        ex = Executor()
        out = tfs.aggregate(s, tfs.group_by(df, "k"), executor=ex)
        (vraw,) = ex._cache.values()
        assert vraw._cache_size() <= 20, vraw._cache_size()
        # correctness at scale
        odf = out.to_pandas().sort_values("k").reset_index(drop=True)
        k = df["k"].values
        x = df["x"].values
        want = np.array([x[k == g].sum() for g in range(400)])
        np.testing.assert_allclose(odf["x"], want, rtol=1e-9)


class TestMultiKeyAggregate:
    """groupBy over several key columns (the reference's
    `df.groupBy(k1, k2).agg`, reachable through `RelationalGroupedDataset`)."""

    def test_two_int_keys(self):
        df = frame_of(
            a=np.array([0, 0, 1, 1, 0]),
            b=np.array([0, 1, 0, 1, 0]),
            x=np.array([1.0, 2.0, 3.0, 4.0, 5.0]),
        )
        s = dsl.reduce_sum(
            tfs.block(df, "x", tf_name="x_input"), axes=[0]
        ).named("x")
        out = tfs.aggregate(s, tfs.group_by(df, "a", "b")).to_pandas()
        out = out.sort_values(["a", "b"]).reset_index(drop=True)
        assert out["x"].tolist() == [6.0, 2.0, 3.0, 4.0]
        assert out["a"].tolist() == [0, 0, 1, 1]
        assert out["b"].tolist() == [0, 1, 0, 1]

    def test_mixed_dtype_keys(self):
        df = frame_of(
            g=np.array([1.5, 1.5, 2.5]),
            h=np.array([7, 8, 7]),
            x=np.array([1.0, 2.0, 3.0]),
        )
        s = dsl.reduce_sum(
            tfs.block(df, "x", tf_name="x_input"), axes=[0]
        ).named("x")
        out = tfs.aggregate(s, tfs.group_by(df, "g", "h")).to_pandas()
        out = out.sort_values(["g", "h"]).reset_index(drop=True)
        assert out["x"].tolist() == [1.0, 2.0, 3.0]

    def test_three_keys_vector_values(self):
        df = frame_of(
            a=np.array([0, 0, 0, 1]),
            b=np.array([0, 0, 1, 0]),
            c=np.array([5, 5, 5, 5]),
            v=np.arange(8.0).reshape(4, 2),
        )
        s = dsl.reduce_sum(
            tfs.block(df, "v", tf_name="v_input"), axes=[0]
        ).named("v")
        out = tfs.aggregate(s, tfs.group_by(df, "a", "b", "c"))
        pdf = out.to_pandas().sort_values(["a", "b"]).reset_index(drop=True)
        np.testing.assert_allclose(
            np.stack(pdf["v"].to_numpy()),
            np.array([[2.0, 4.0], [4.0, 5.0], [6.0, 7.0]]),
        )


class TestFunctionEmptyOutputDict:
    """A function graph returning an empty dict must fail at the verb
    with the cause named — previously the trim path sailed through and
    exploded later in np.cumsum over a None block size."""

    def test_map_blocks_trim_empty_dict_verb_error(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(8.0)}, num_blocks=2)
        with pytest.raises(ValueError, match="empty dict"):
            tfs.map_blocks(lambda x: {}, df, trim=True)

    def test_map_rows_empty_dict_verb_error(self):
        df = tfs.TensorFrame.from_dict({"x": np.arange(8.0)})
        with pytest.raises(ValueError, match="empty dict"):
            tfs.map_rows(lambda x: {}, df)
