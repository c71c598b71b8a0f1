"""Lazy verb fusion: LazyFrame plans, graph splicing, terminal forcing.

The fusion contract (ISSUE 2 / HiFrames, arxiv 1704.02341): a chained
``map_blocks -> map_blocks -> reduce_blocks`` pipeline deferred under
`tfs.lazy()` / `df.lazy()` compiles to ONE XLA program per block — the
executor cache gains exactly one "block"-kind entry keyed on the fused
graph's fingerprint — and the results are bit-identical to the eager
chain."""

from collections import Counter

import numpy as np
import pytest

import jax

import tensorframes_tpu as tfs
from tensorframes_tpu import dsl
from tensorframes_tpu.lazy import LazyFrame, LazyPlan
from tensorframes_tpu.runtime.executor import Executor
from tensorframes_tpu.schema import ScalarType, Shape
from tensorframes_tpu.utils.profiling import reset_stats, stats


def _frame(rows=24, blocks=3, dtype=np.float32):
    return tfs.TensorFrame.from_dict(
        {"x": np.arange(rows, dtype=dtype)}, num_blocks=blocks
    )


def _eager_chain(df, executor=None):
    m1 = tfs.map_blocks(
        (tfs.block(df, "x") * 2.0 + 1.0).named("y"), df, executor=executor
    )
    m2 = tfs.map_blocks(
        (tfs.block(m1, "y") * 3.0).named("z"), m1, executor=executor
    )
    return m2


def _lazy_chain(df, executor=None):
    lf = df.lazy()
    lf = lf.map_blocks(
        (tfs.block(lf, "x") * 2.0 + 1.0).named("y"), executor=executor
    )
    lf = lf.map_blocks(
        (tfs.block(lf, "y") * 3.0).named("z"), executor=executor
    )
    return lf


def _sum_of(frame_like, col):
    ph = tfs.block(frame_like, col, tf_name=col + "_input")
    return dsl.reduce_sum(ph, axes=[0]).named(col)


class TestSpliceCorrectness:
    def test_force_matches_eager_chain_bitwise(self):
        df = _frame()
        eager = _eager_chain(df)
        forced = _lazy_chain(df).force()
        for col in ("y", "z", "x"):
            np.testing.assert_array_equal(
                np.asarray(forced[col].values),
                np.asarray(eager[col].values),
            )

    def test_reduce_terminal_matches_eager_bitwise(self):
        df = _frame()
        eager = tfs.reduce_blocks(_sum_of(_eager_chain(df), "z"), _eager_chain(df))
        lf = _lazy_chain(df)
        lazy = lf.reduce_blocks(_sum_of(lf, "z"))
        assert np.asarray(lazy) == np.asarray(eager)

    def test_single_block_no_combine(self):
        df = _frame(rows=10, blocks=1)
        lf = _lazy_chain(df)
        r = lf.reduce_blocks(_sum_of(lf, "z"))
        expect = ((np.arange(10.0) * 2 + 1) * 3).sum()
        assert float(np.asarray(r)) == pytest.approx(expect)

    def test_multi_fetch_reduce_feed_order(self):
        # fetches (s, m) sort as feeds (m_input, s_input): the combine
        # must re-key partials by NAME, not position
        df = _frame()
        lf = _lazy_chain(df)
        s = dsl.reduce_sum(
            dsl.placeholder(ScalarType.float32, Shape((None,)), name="s_input"),
            axes=[0],
        ).named("s")
        m = dsl.reduce_max(
            dsl.placeholder(ScalarType.float32, Shape((None,)), name="m_input"),
            axes=[0],
        ).named("m")
        out = lf.reduce_blocks(
            [s, m], feed_dict={"s_input": "z", "m_input": "z"}
        )
        z = (np.arange(24, dtype=np.float32) * 2 + 1) * 3
        assert float(np.asarray(out["s"])) == pytest.approx(float(z.sum()))
        assert float(np.asarray(out["m"])) == pytest.approx(float(z.max()))

    def test_shadowing_graph_output_wins(self):
        # a later stage that re-defines an existing virtual column
        # shadows it, exactly like the eager output-frame rule (graph
        # output wins)
        df = _frame()
        lf = df.lazy().map_blocks((tfs.block(df, "x") * 2.0).named("y"))
        lf = lf.map_blocks((tfs.block(lf, "x") + 5.0).named("y"))
        forced = lf.force()
        np.testing.assert_array_equal(
            np.asarray(forced["y"].values),
            np.arange(24, dtype=np.float32) + 5.0,
        )
        assert forced.columns == ["y", "x"]

    def test_empty_blocks_skipped(self):
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(6.0, dtype=np.float32)}
        )
        df = tfs.TensorFrame(
            [df["x"]], [0, 0, 3, 3, 6]
        )  # two empty blocks
        lf = df.lazy().map_blocks((tfs.block(df, "x") * 2.0).named("y"))
        forced = lf.force()
        np.testing.assert_array_equal(
            np.asarray(forced["y"].values), np.arange(6.0, dtype=np.float32) * 2
        )
        r = lf.reduce_blocks(_sum_of(lf, "y"))
        assert float(np.asarray(r)) == pytest.approx(30.0)


class TestNameCollisions:
    def test_anonymous_node_uniquification(self):
        # both stages emit anonymous Mul/Const nodes with identical
        # names; splice must uniquify, and results stay correct
        df = _frame()
        lf = df.lazy()
        lf = lf.map_blocks((tfs.block(lf, "x") * 2.0).named("y"))
        lf = lf.map_blocks((tfs.block(lf, "y") * 2.0).named("z"))
        plan = lf.plan()
        names = [n.name for n in plan.graph.nodes]
        assert len(names) == len(set(names))
        assert any(n.endswith("__f1") for n in names), names
        np.testing.assert_array_equal(
            np.asarray(lf.force()["z"].values),
            np.arange(24, dtype=np.float32) * 4.0,
        )

    def test_explicit_same_name_stages(self):
        df = _frame()
        lf = df.lazy()
        lf = lf.map_blocks((tfs.block(lf, "x") + 1.0).named("t"))
        lf2 = lf.map_blocks((tfs.block(lf, "t") + 1.0).named("u"))
        np.testing.assert_array_equal(
            np.asarray(lf2.force()["u"].values),
            np.arange(24, dtype=np.float32) + 2.0,
        )


class TestSpliceTimeValidation:
    def test_dtype_mismatch_raises_at_splice(self):
        df = _frame(dtype=np.float32)
        lf = df.lazy().map_blocks((tfs.block(df, "x") * 2.0).named("y"))
        bad = dsl.placeholder(ScalarType.float64, Shape((None,)), name="y")
        with pytest.raises(ValueError, match="dtype"):
            lf.map_blocks((bad + 1.0).named("z"))  # raises HERE, not at force

    def test_shape_mismatch_raises_at_splice(self):
        df = _frame()
        lf = df.lazy().map_blocks((tfs.block(df, "x") * 2.0).named("y"))
        bad = dsl.placeholder(
            ScalarType.float32, Shape((None, 3)), name="y"
        )
        with pytest.raises(ValueError, match="shape|compatible"):
            lf.map_blocks((bad + 1.0).named("z"))

    def test_unknown_column_raises_at_splice(self):
        df = _frame()
        lf = df.lazy()
        ph = dsl.placeholder(ScalarType.float32, Shape((None,)), name="nope")
        with pytest.raises(ValueError, match="nope"):
            lf.map_blocks((ph + 1.0).named("z"))

    def test_trim_and_bindings_rejected(self):
        df = _frame()
        lf = df.lazy()
        t = (tfs.block(df, "x") * 2.0).named("y")
        with pytest.raises(ValueError, match="trim"):
            lf.map_blocks(t, trim=True)
        with pytest.raises(ValueError, match="bindings"):
            lf.map_blocks(t, bindings={"x": np.zeros(3, np.float32)})


class TestTerminalForcing:
    def test_reduce_rows_forces(self):
        df = _frame()
        lf = _lazy_chain(df)
        z1 = dsl.placeholder(ScalarType.float32, Shape(()), name="z_1")
        z2 = dsl.placeholder(ScalarType.float32, Shape(()), name="z_2")
        r = tfs.reduce_rows((z1 + z2).named("z"), lf)
        expect = ((np.arange(24, dtype=np.float32) * 2 + 1) * 3).sum()
        assert float(np.asarray(r)) == pytest.approx(float(expect), rel=1e-5)

    def test_aggregate_forces(self):
        df = tfs.TensorFrame.from_dict(
            {
                "k": np.array([0, 0, 1, 1, 2, 2], dtype=np.int64),
                "x": np.arange(6, dtype=np.float32),
            },
            num_blocks=2,
        )
        lf = df.lazy().map_blocks((tfs.block(df, "x") * 10.0).named("v"))
        out = tfs.aggregate(
            _sum_of(lf, "v"), lf.group_by("k")
        )
        got = {
            int(k): float(v)
            for k, v in zip(
                out.host_values("k"), np.asarray(out.host_values("v"))
            )
        }
        assert got == {0: 10.0, 1: 50.0, 2: 90.0}

    def test_host_values_collect_to_pandas_force(self):
        df = _frame()
        lf = _lazy_chain(df)
        expect = (np.arange(24, dtype=np.float32) * 2 + 1) * 3
        np.testing.assert_array_equal(np.asarray(lf.host_values("z")), expect)
        rows = lf.collect()
        assert len(rows) == 24 and float(rows[3]["z"]) == float(expect[3])
        pdf = lf.to_pandas()
        np.testing.assert_allclose(pdf["z"].to_numpy(), expect)

    def test_force_is_cached(self):
        df = _frame()
        lf = _lazy_chain(df)
        f1 = lf.force()
        f2 = lf.force()
        assert f1 is f2

    def test_module_level_verbs_route_lazyframe(self):
        df = _frame()
        lf = df.lazy()
        lf = tfs.map_blocks((tfs.block(lf, "x") * 2.0).named("y"), lf)
        assert isinstance(lf, LazyFrame)
        r = tfs.reduce_blocks(_sum_of(lf, "y"), lf)
        assert float(np.asarray(r)) == pytest.approx(
            float(np.arange(24.0).sum() * 2)
        )


class TestCacheKeying:
    def test_one_block_program_vs_eager_n(self):
        df = _frame(rows=4000, blocks=4)
        exf, exe = Executor(), Executor()
        lf = _lazy_chain(df, executor=exf)
        lf.reduce_blocks(_sum_of(lf, "z"), executor=exf)
        m = _eager_chain(df, executor=exe)
        tfs.reduce_blocks(_sum_of(m, "z"), m, executor=exe)
        fused_kinds = Counter(k[0] for k in exf.cache_keys())
        eager_kinds = Counter(k[0] for k in exe.cache_keys())
        # the whole 3-verb pipeline is ONE fused per-block program (the
        # reduce terminal runs it as a "block-bucketed" masked program
        # under the default shape policy, "block" with bucketing off)...
        assert fused_kinds["block"] + fused_kinds["block-bucketed"] == 1
        # ...where the eager chain compiled one per verb
        assert eager_kinds["block"] + eager_kinds["block-bucketed"] == 3

    def test_fused_fingerprint_second_run_zero_misses(self):
        df = _frame()
        ex = Executor()

        def run():
            lf = _lazy_chain(df, executor=ex)
            return lf.reduce_blocks(_sum_of(lf, "z"), executor=ex)

        r1 = run()
        misses = ex.cache_misses
        r2 = run()  # freshly spliced graph, identical fused fingerprint
        assert ex.cache_misses == misses
        assert np.asarray(r1) == np.asarray(r2)

    def test_fused_chain_issues_no_host_sync(self):
        """Repeated fused runs on a device-resident frame fetch nothing
        to the host: the lazy plan never materialises an intermediate,
        and every run equals the eager chain."""
        df = _frame(rows=4000, blocks=4).to_device()
        ex = Executor()
        m = _eager_chain(df, executor=ex)
        eager = tfs.reduce_blocks(_sum_of(m, "z"), m, executor=ex)

        def fused():
            lf = _lazy_chain(df, executor=ex)
            return lf.reduce_blocks(_sum_of(lf, "z"), executor=ex)

        fused()  # compiles out of the counted runs
        reset_stats()
        outs = [jax.block_until_ready(fused()) for _ in range(3)]
        assert stats().get("host_sync", 0) == 0
        for out in outs:
            assert isinstance(out, jax.Array)
            assert np.asarray(out) == np.asarray(eager)


class TestLazyModeAndPlan:
    def test_context_manager_defers_and_restores(self):
        df = _frame()
        with tfs.lazy():
            out = tfs.map_blocks((tfs.block(df, "x") + 1.0).named("y"), df)
            assert isinstance(out, LazyFrame)
        eager = tfs.map_blocks((tfs.block(df, "x") + 1.0).named("y"), df)
        assert isinstance(eager, tfs.TensorFrame)
        np.testing.assert_array_equal(
            np.asarray(out.host_values("y")), np.asarray(eager["y"].values)
        )

    def test_function_frontend_stays_eager_under_mode(self):
        df = _frame()
        with tfs.lazy():
            out = tfs.map_blocks(lambda x: {"y": x + 1.0}, df)
        assert isinstance(out, tfs.TensorFrame)

    def test_bytes_passthrough_stays_eager_under_mode(self):
        # string placeholders cannot splice; under the MODE the call
        # must fall through to the eager path, not raise
        df = tfs.TensorFrame.from_dict(
            {
                "x": np.arange(4, dtype=np.float32),
                "s": [b"a", b"b", b"c", b"d"],
            }
        )
        y = (tfs.block(df, "x") + 1.0).named("y")
        s = dsl.identity(
            dsl.placeholder(ScalarType.string, Shape(()), name="s")
        ).named("t")
        with tfs.lazy():
            out = tfs.map_blocks([y, s], df)
        assert isinstance(out, tfs.TensorFrame)
        np.testing.assert_array_equal(
            np.asarray(out["y"].values), np.arange(4, dtype=np.float32) + 1
        )
        assert list(out["t"].rows())[0] == b"a"

    def test_library_collision_refuses_to_fuse(self):
        # two stages carrying the same function NAME with different
        # bodies must refuse to splice, not silently pick one
        from tensorframes_tpu.graph.fuse import splice
        from tensorframes_tpu.graph.ir import Graph, GraphNode
        from tensorframes_tpu.proto.graphdef import (
            ArgDef,
            AttrValue,
            FunctionDef,
        )

        def lib_graph(mul_by):
            g = Graph(
                [
                    GraphNode(
                        "p",
                        "Placeholder",
                        [],
                        {
                            "dtype": AttrValue.of_type(ScalarType.float32),
                            "shape": AttrValue.of_shape(Shape((None,))),
                        },
                    )
                ]
            )
            g.library = {
                "f": FunctionDef(
                    name="f",
                    input_args=[ArgDef("a", ScalarType.float32)],
                    output_args=[ArgDef("o", ScalarType.float32)],
                    nodes=[GraphNode(f"mul{mul_by}", "Mul", ["a", "a"]).to_node_def()],
                    ret={"o": f"mul{mul_by}:z:0"},
                )
            }
            return g

        with pytest.raises(ValueError, match="collision"):
            splice(lib_graph(2), lib_graph(3), {}, ["p"])

    def test_explain_renders_stage_provenance(self):
        lf = _lazy_chain(_frame())
        text = tfs.explain(lf)
        assert "stage 1: map_blocks -> [y]" in text
        assert "stage 2: map_blocks -> [z]" in text
        assert "feed: x <- column 'x'" in text
        plan = tfs.explain_detailed(lf)
        assert isinstance(plan, LazyPlan)
        assert [s.outputs for s in plan.stages] == [("y",), ("z",)]
        assert plan.feeds == {"x": "x"}
        assert set(plan.sources) == {"y", "z"}

    def test_virtual_schema_matches_forced_schema(self):
        lf = _lazy_chain(_frame())
        forced = lf.force()
        assert lf.columns == forced.columns
        assert [c.dtype for c in lf.info] == [c.dtype for c in forced.info]


class TestStreamingFusedChunks:
    def test_stream_of_lazy_chunks_matches_eager(self):
        def chunks(lazy_mode):
            for lo in range(0, 40, 10):
                df = tfs.TensorFrame.from_dict(
                    {"x": np.arange(lo, lo + 10, dtype=np.float32)},
                    num_blocks=2,
                )
                if lazy_mode:
                    yield df.lazy().map_blocks(
                        (tfs.block(df, "x") * 2.0).named("y")
                    )
                else:
                    yield tfs.map_blocks(
                        (tfs.block(df, "x") * 2.0).named("y"), df
                    )

        ph = dsl.placeholder(
            ScalarType.float32, Shape((None,)), name="y_input"
        )
        fetch = dsl.reduce_sum(ph, axes=[0]).named("y")
        r_lazy = tfs.reduce_blocks_stream(fetch, chunks(True))
        fetch2 = dsl.reduce_sum(
            dsl.placeholder(
                ScalarType.float32, Shape((None,)), name="y_input"
            ),
            axes=[0],
        ).named("y")
        r_eager = tfs.reduce_blocks_stream(fetch2, chunks(False))
        assert float(np.asarray(r_lazy)) == pytest.approx(
            float(np.asarray(r_eager))
        )
        assert float(np.asarray(r_lazy)) == pytest.approx(
            float(np.arange(40.0).sum() * 2)
        )


class TestMeshFusion:
    def _mesh(self):
        try:
            from tensorframes_tpu.parallel import data_mesh
        except Exception as e:  # jax pin without jax.shard_map
            pytest.skip(f"mesh layer unavailable: {e}")
        if len(jax.devices()) < 2:
            pytest.skip("needs the virtual multi-device CPU mesh")
        return data_mesh()

    def test_fused_force_on_mesh(self):
        mesh = self._mesh()
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(19, dtype=np.float32)}  # remainder tail
        )
        lf = df.lazy()
        lf = lf.map_blocks((tfs.block(lf, "x") * 2.0).named("y"))
        lf = lf.map_blocks((tfs.block(lf, "y") + 1.0).named("z"))
        forced = lf.force(mesh=mesh)
        np.testing.assert_array_equal(
            np.asarray(forced["z"].values),
            np.arange(19, dtype=np.float32) * 2.0 + 1.0,
        )

    def test_fused_reduce_on_mesh(self):
        mesh = self._mesh()
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(19, dtype=np.float32)}
        )
        lf = df.lazy().map_blocks((tfs.block(df, "x") * 2.0).named("y"))
        r = lf.reduce_blocks(_sum_of(lf, "y"), mesh=mesh)
        assert float(np.asarray(r)) == pytest.approx(
            float(np.arange(19.0).sum() * 2)
        )
