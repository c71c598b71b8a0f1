"""NativeExecutor execution-kind coverage without the plugin .so.

The real host tests (test_pjrt_host.py) need a built PJRT plugin .so.
This suite pins
everything ABOVE the C ABI — the lowering recipes, input/output pytree
flattening, per-shape executable caching, and the mesh-kind refusal —
against an in-process CPU PJRT client that compiles the exact same
StableHLO text the native host would receive.
"""

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import dsl
from tensorframes_tpu.runtime.native_executor import NativeExecutor
from tensorframes_tpu.schema import ScalarType, Shape


class InProcessCpuHost:
    """Duck-typed PjrtHost: compiles StableHLO text with the in-process
    CPU PJRT client, executes with numpy in/out — the same contract as
    native/pjrt_host.cc minus the C ABI."""

    platform = "cpu"
    device_count = 1

    def compile(self, stablehlo: str):
        import jax
        from jax._src import xla_bridge
        from jax._src.interpreters import mlir as jmlir
        from jax._src.lib import xla_client
        from jax._src.lib.mlir import ir
        from jaxlib import _jax

        backend = xla_bridge.get_backend("cpu")
        with jmlir.make_ir_context():
            module = ir.Module.parse(stablehlo)
            devs = _jax.DeviceList(tuple(backend.local_devices()[:1]))
            exe = backend.compile_and_load(
                module, devs, xla_client.CompileOptions()
            )

        def run(*inputs, out_specs):
            import jax

            res = exe.execute_sharded(
                [jax.device_put(np.asarray(a)) for a in inputs]
            )
            outs = res.disassemble_into_single_device_arrays()
            got = [np.asarray(o[0]) for o in outs]
            assert len(got) == len(out_specs)
            for g, (shape, dtype) in zip(got, out_specs):
                assert g.shape == tuple(shape), (g.shape, shape)
                assert g.dtype == np.dtype(dtype), (g.dtype, dtype)
            return got

        return run


@pytest.fixture()
def ex():
    return NativeExecutor.for_host(InProcessCpuHost())


class TestNativeExecutorKinds:
    def test_map_blocks_block_kind(self, ex):
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(6, dtype=np.float32)}, num_blocks=2
        )
        z = (tfs.block(df, "x") + 3.0).named("z")
        out = tfs.map_blocks(z, df, executor=ex)
        np.testing.assert_array_equal(
            np.asarray(out["z"].values), np.arange(6.0, dtype=np.float32) + 3
        )
        assert ex.compile_count >= 1

    def test_map_rows_vmap_kind(self, ex):
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(8, dtype=np.float32).reshape(4, 2)}
        )
        y = (tfs.row(df, "x") * 2.0).named("y")
        out = tfs.map_rows(y, df, executor=ex)
        np.testing.assert_array_equal(
            np.asarray(out["y"].values),
            np.arange(8, dtype=np.float32).reshape(4, 2) * 2,
        )
        assert ex._jax_fallback is None

    def test_reduce_rows_fold_kind_dict_pytree(self, ex):
        # the fold kind feeds a DICT pytree: flattening order must match
        # the lowered module's parameter order
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(1, 6, dtype=np.float64)}, num_blocks=2
        )
        x1 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_1")
        x2 = dsl.placeholder(ScalarType.float64, Shape(()), name="x_2")
        out = tfs.reduce_rows(dsl.add(x1, x2).named("x"), df, executor=ex)
        assert float(out) == 15.0
        assert ex._jax_fallback is None

    def test_aggregate_segment_kind(self, ex):
        df = tfs.TensorFrame.from_dict(
            {
                "key": np.array([0, 1, 0, 1, 0], dtype=np.int64),
                "x": np.array([1.0, 10.0, 2.0, 20.0, 3.0], np.float64),
            }
        )
        x_input = tfs.block(df, "x", tf_name="x_input")
        x = dsl.reduce_sum(x_input, axes=[0]).named("x")
        out = tfs.aggregate(x, tfs.group_by(df, "key"), executor=ex)
        np.testing.assert_allclose(
            np.asarray(out["x"].values), np.array([6.0, 30.0])
        )
        assert ex._jax_fallback is None

    def test_reduce_blocks_kind(self, ex):
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(10, dtype=np.float64)}, num_blocks=3
        )
        x_input = tfs.block(df, "x", tf_name="x_input")
        x = dsl.reduce_sum(x_input, axes=[0]).named("x")
        assert float(tfs.reduce_blocks(x, df, executor=ex)) == 45.0

    def test_per_shape_executable_cache(self, ex):
        df1 = tfs.TensorFrame.from_dict({"x": np.arange(4, dtype=np.float32)})
        # 4 and 20 rows pad to different rungs of the bucket ladder (8
        # and 32): sizes inside one rung share one executable by design
        df2 = tfs.TensorFrame.from_dict({"x": np.arange(20, dtype=np.float32)})
        z = (tfs.block(df1, "x") + 1.0).named("z")
        tfs.map_blocks(z, df1, executor=ex)
        n = ex.compile_count
        tfs.map_blocks(z, df1, executor=ex)  # same shape: cached
        assert ex.compile_count == n
        tfs.map_blocks(z, df2, executor=ex)  # new rung: one more compile
        assert ex.compile_count == n + 1

    def test_unused_input_still_executes(self, ex):
        # a graph placeholder the fetches never read: the lowered module
        # must still accept the full feed list (keep_unused) instead of
        # dying with a buffer-count mismatch at execute time
        a = dsl.placeholder(ScalarType.float64, Shape((None,)), name="a")
        g, fl = dsl.build([dsl.identity(a).named("z")])
        # feed list includes "b", which the fetch subgraph never reads:
        # jit would DCE it out of the module without keep_unused, and
        # the executor would then send one buffer too many

        def traceable(a_arr, b_arr):
            from tensorframes_tpu.ops.lowering import build_callable

            return build_callable(g, fl, ["a"])(a_arr)

        fn = ex._native_run(traceable)
        (out,) = fn(np.arange(3.0), np.arange(3.0) + 10)
        np.testing.assert_array_equal(np.asarray(out), np.arange(3.0))

    def test_mesh_kind_refused_without_fallback(self, ex):
        class G:
            def fingerprint(self):
                return "g"

        with pytest.raises(NotImplementedError, match="shard_map"):
            ex.cached("shmap-8-[p]", G(), ("z",), ("x",), lambda: None)
