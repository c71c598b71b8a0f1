"""Aux subsystems: config, profiling stats, checkpoint/resume."""

import os

import numpy as np
import pytest

import tensorframes_tpu as tfs
from tensorframes_tpu import config
from tensorframes_tpu.utils import (
    load_frame,
    load_params,
    reset_stats,
    save_frame,
    save_params,
    stats,
)


class TestConfig:
    def test_defaults(self):
        assert config.get().matmul_precision == "highest"
        assert config.get().aggregate_buffer_rows == 10

    def test_override_scoped(self):
        with config.override(matmul_precision="default"):
            assert config.get().matmul_precision == "default"
            from jax import lax

            assert config.get().lax_precision() == lax.Precision.DEFAULT
        assert config.get().matmul_precision == "highest"

    def test_unknown_key_rejected(self):
        with pytest.raises(AttributeError):
            config.update(nonsense=1)


class TestStats:
    def test_verb_counters(self):
        reset_stats()
        df = tfs.TensorFrame.from_dict({"x": np.arange(5.0)})
        z = (tfs.block(df, "x") + 1.0).named("z")
        tfs.map_blocks(z, df)
        s = stats()
        assert s["map_blocks.calls"] == 1
        assert s["map_blocks.rows"] == 5
        assert s["map_blocks.seconds"] > 0


class TestCheckpoint:
    def test_frame_roundtrip(self, tmp_path):
        df = tfs.TensorFrame.from_dict(
            {
                "x": np.arange(6.0),
                "v": [np.arange(2.0), np.arange(3.0)] * 3,
            },
            num_blocks=3,
        )
        p = str(tmp_path / "frame.npz")
        save_frame(p, df)
        back = load_frame(p)
        assert back.offsets == df.offsets
        assert back.columns == df.columns
        np.testing.assert_array_equal(back["x"].values, df["x"].values)
        assert not back["v"].is_dense
        np.testing.assert_array_equal(back["v"].row(1), [0.0, 1.0, 2.0])

    def test_device_frame_roundtrip(self, tmp_path):
        df = tfs.TensorFrame.from_dict({"x": np.arange(4.0)}).to_device()
        p = str(tmp_path / "dev.npz")
        save_frame(p, df)
        back = load_frame(p)
        np.testing.assert_array_equal(np.asarray(back["x"].values), np.arange(4.0))

    def test_params_roundtrip_orbax(self, tmp_path):
        from tensorframes_tpu.models import MLP

        m = MLP([4, 8, 2], seed=0)
        p = str(tmp_path / "ckpt")
        save_params(p, m.params)
        like = [(np.zeros_like(w), np.zeros_like(b)) for w, b in m.params]
        back = load_params(p, like)
        np.testing.assert_array_equal(
            np.asarray(back[0][0]), np.asarray(m.params[0][0])
        )

    def test_resume_training(self, tmp_path):
        # the actual resume story: train, checkpoint, restore, continue
        import jax
        import jax.numpy as jnp

        from tensorframes_tpu.models import MLP

        m = MLP([4, 8, 2], seed=0)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.rand(16, 4), jnp.float32)
        y = jnp.asarray(rng.randint(0, 2, 16))
        step = jax.jit(lambda p, x, y: m.train_step(p, x, y, lr=0.1))
        params = m.params
        for _ in range(3):
            params, loss = step(params, x, y)
        ck = str(tmp_path / "resume")
        save_params(ck, params)
        like = [(np.zeros_like(w), np.zeros_like(b)) for w, b in params]
        restored = load_params(ck, like)
        p1, l1 = step(params, x, y)
        p2, l2 = step(restored, x, y)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


class TestFluentAPI:
    def test_fluent_verbs(self):
        import tensorframes_tpu as tfs
        from tensorframes_tpu import dsl

        df = tfs.TensorFrame.from_dict({"x": np.arange(4.0)})
        out = df.map_blocks((df.block("x") + 1.0).named("z"))
        np.testing.assert_array_equal(out["z"].values, np.arange(4.0) + 1)
        x_input = df.block("x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        assert float(df.reduce_blocks(s)) == 6.0

    def test_fluent_groupby_aggregate(self):
        import tensorframes_tpu as tfs
        from tensorframes_tpu import dsl

        df = tfs.TensorFrame.from_dict(
            {"k": np.array([0, 0, 1], np.int64), "x": np.array([1.0, 2.0, 5.0])}
        )
        x_input = df.block("x", tf_name="x_input")
        s = dsl.reduce_sum(x_input, axes=[0]).named("x")
        out = df.group_by("k").aggregate(s)
        got = dict(zip(out["k"].values.tolist(), out["x"].values.tolist()))
        assert got == {0: 3.0, 1: 5.0}


class TestRetry:
    """Classified retry (`runtime.faults`): TRANSIENT errors consume
    attempts with backoff; deterministic errors fail after exactly one
    attempt (the old blanket retry burned all N attempts on them)."""

    def test_flaky_block_recovers(self):
        from tensorframes_tpu import config

        calls = {"n": 0}

        def flaky(x):
            calls["n"] += 1
            if calls["n"] == 1:
                # a transient-classified runtime status (the XLA
                # "device went away" family)
                raise RuntimeError("UNAVAILABLE: injected device loss")
            return {"y": x + 1.0}

        with config.override(retry_backoff_base_s=0.001):
            from tensorframes_tpu.runtime.retry import run_with_retries

            out = run_with_retries(flaky, np.arange(3.0), attempts=2)
        np.testing.assert_array_equal(out["y"], np.arange(3.0) + 1)
        assert calls["n"] == 2

    def test_transient_exhausted_raises_original(self):
        from tensorframes_tpu import config
        from tensorframes_tpu.runtime.retry import run_with_retries

        calls = {"n": 0}

        def always_unavailable():
            calls["n"] += 1
            raise RuntimeError("UNAVAILABLE: still down")

        with config.override(retry_backoff_base_s=0.001):
            with pytest.raises(RuntimeError, match="still down"):
                run_with_retries(always_unavailable, attempts=2)
        assert calls["n"] == 3  # 1 attempt + 2 transient retries

    def test_deterministic_fails_after_one_attempt(self):
        """Regression (ISSUE 6 satellite): deterministic errors — e.g.
        `FloatingPointError` from check_numerics, dtype/shape
        mismatches — must NOT burn the retry budget; the original
        exception surfaces after exactly one attempt."""
        from tensorframes_tpu.runtime.retry import run_with_retries

        for exc in (
            ValueError("deterministic"),
            FloatingPointError("fetch 'z' contains 1 non-finite value"),
            TypeError("deterministic"),
        ):
            calls = {"n": 0}

            def fails():
                calls["n"] += 1
                raise exc

            with pytest.raises(type(exc)):
                run_with_retries(fails, attempts=5)
            assert calls["n"] == 1, type(exc)


class TestLogging:
    def test_logger_level_env(self, monkeypatch):
        import importlib

        from tensorframes_tpu.utils import log as tlog

        lg = tlog.get_logger("test")
        assert lg.name == "tensorframes_tpu.test"


class TestChipEntryPoints:
    """`chip_smoke.py` is what runs on the TPU besides the benchmark
    (`perf/`). Here, on the CPU, the smoke is rehearsed at tiny sizes and
    shown to refuse a machine with no TPU instead of measuring its CPU."""

    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _run(self, script, *args, **env):
        import subprocess
        import sys

        return subprocess.run(
            [sys.executable, os.path.join(self.ROOT, script), *args],
            capture_output=True, text=True, timeout=600, cwd=self.ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        )

    def test_chip_smoke_rehearsal(self, tmp_path):
        import json

        cache = tmp_path / "cache"
        proc = self._run(
            "chip_smoke.py", "--rehearse", "--out", str(tmp_path / "out"),
            JAX_COMPILATION_CACHE_DIR=str(cache),
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
        phases = {ln["phase"] for ln in lines}
        assert phases >= {
            "start", "a_readme", "c_reduce_blocks",
            "c_reduce_stream", "d_aggregate_16_keys", "d_aggregate_300_keys",
            "f_inception_v3", "g_serving", "h_plan",
            "i_flash_attention_float32", "i_flash_attention_bfloat16",
            "i_transformer_train_step", "total",
        }, phases
        # a rehearsal is never reported as a chip run
        assert not any(ln.get("ok") for ln in lines)
        assert lines[-1]["rehearsal"] == "passed"
        assert lines[-1]["device"]["platform"] == "cpu"
        # the compile cache went where the variable said, nowhere else
        assert lines[0]["compilation_cache_dir"] == str(cache)
        assert any(cache.iterdir())

    def test_serving_phase_alone_in_a_fresh_process(self, tmp_path):
        # 8 concurrent clients against a process whose FIRST pyarrow use
        # is the serving wire path: segfaulted inside pyarrow until
        # `serving.serve()` imported it on the mounting thread
        proc = self._run(
            "chip_smoke.py", "--rehearse", "--phases", "g",
            "--out", str(tmp_path / "out"),
            JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert '"phase": "g_serving"' in proc.stdout

    @pytest.mark.parametrize("script", ["chip_smoke.py"])
    def test_no_tpu_is_an_error_not_a_cpu_number(self, script, tmp_path):
        proc = self._run(script)
        assert proc.returncode != 0
        assert proc.stdout.strip() == "", proc.stdout
        assert "needs a TPU" in proc.stderr


class TestCostAnalysis:
    """XLA cost model surfaced per compiled verb program (SURVEY §5:
    the reference has StepStats protos but nothing consumes them)."""

    def test_matmul_flops_scale(self):
        df = tfs.TensorFrame.from_dict(
            {"x": np.random.RandomState(0).rand(64, 32).astype(np.float32)}
        )
        from tensorframes_tpu import dsl

        w = dsl.constant(np.ones((32, 16), np.float32), name="w")
        z = dsl.matmul(tfs.block(df, "x"), w).named("z")
        cost = tfs.cost_analysis(z, df)
        # 64x32 @ 32x16 = 2*64*32*16 = 65536 flops at minimum
        assert cost["flops"] >= 2 * 64 * 32 * 16
        assert cost["block_rows"] == 64
        assert cost["flops_per_row"] == cost["flops"] / 64
        assert cost["bytes_accessed"] > 0

    def test_elementwise_is_bandwidth_bound(self):
        df = tfs.TensorFrame.from_dict(
            {"x": np.arange(1024, dtype=np.float32)}
        )
        z = (tfs.block(df, "x") + 3.0).named("z")
        cost = tfs.cost_analysis(z, df)
        # x+3 over 1024 floats: ~1 flop/elem, >= 8 bytes/elem moved
        assert cost["flops"] <= 4 * 1024
        assert cost["bytes_accessed"] >= 2 * 4 * 1024

    def test_empty_frame_rejected(self):
        from tensorframes_tpu.frame import Column, TensorFrame

        df = TensorFrame([Column("x", np.zeros((0,)))], offsets=[0, 0])
        z = (tfs.block(df, "x") + 1.0).named("z")
        with pytest.raises(ValueError, match="no non-empty block"):
            tfs.cost_analysis(z, df)


class TestShardedCheckpoint:
    """Checkpoint/resume for mesh-sharded params: a distributed training
    state must restore with its shardings intact (SURVEY §5 designed-
    fresh subsystem; the reference has no checkpointing at all)."""

    def test_sharded_params_roundtrip(self, tmp_path):
        import jax

        from tensorframes_tpu.models import MLP
        from tensorframes_tpu.parallel import mesh_2d

        mesh = mesh_2d(2, 2)
        model = MLP([8, 16, 4], seed=0)
        sharded = model.shard_params(model.params, mesh)
        path = str(tmp_path / "ckpt")
        save_params(path, sharded)
        restored = load_params(path, like=sharded)

        flat_a = jax.tree_util.tree_leaves(sharded)
        flat_b = jax.tree_util.tree_leaves(restored)
        assert len(flat_a) == len(flat_b)
        for a, b in zip(flat_a, flat_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            if hasattr(a, "sharding") and hasattr(b, "sharding"):
                assert a.sharding.is_equivalent_to(b.sharding, a.ndim), (
                    a.sharding, b.sharding,
                )

    def test_training_resumes_identically(self, tmp_path):
        from tensorframes_tpu.models import MLP
        from tensorframes_tpu.parallel import mesh_2d

        mesh = mesh_2d(2, 2)
        model = MLP([8, 16, 4], seed=1)
        step = model.sharded_train_step(mesh, lr=0.1)
        params = model.shard_params(model.params, mesh)
        rng = np.random.RandomState(0)
        x = rng.rand(8, 8).astype(np.float32)
        y = rng.randint(0, 4, 8)

        params, _ = step(params, x, y)
        path = str(tmp_path / "mid")
        save_params(path, params)
        params, loss_a = step(params, x, y)

        resumed = load_params(path, like=params)
        resumed, loss_b = step(resumed, x, y)
        np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)


class TestThreadSafety:
    """Race-detection coverage (SURVEY §5): the reference documents its
    DSL as thread-UNSAFE (`Paths.scala:10-12`) and disables parallel test
    execution as mitigation. Here concurrent graph building and verb
    execution must be correct by construction."""

    def test_concurrent_dsl_building(self):
        import threading

        from tensorframes_tpu import dsl
        from tensorframes_tpu.graph import builder

        errors = []

        def build_one(tid):
            try:
                for i in range(20):
                    with builder.scope(f"t{tid}"):
                        x = dsl.placeholder(
                            tfs.ScalarType.float64, tfs.Shape((None,)),
                            name=f"x{tid}_{i}",
                        )
                        z = (x + float(tid)).named(f"z{tid}_{i}")
                        g, fetches = builder.build(z)
                        names = {n.name for n in g.nodes}
                        assert any(f"x{tid}_{i}" in n for n in names), names
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [
            threading.Thread(target=build_one, args=(t,)) for t in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors

    def test_concurrent_verb_execution_shared_executor(self):
        import threading

        from tensorframes_tpu import dsl

        errors = []

        def run_one(tid):
            try:
                data = np.arange(64.0) + tid
                df = tfs.TensorFrame.from_dict({"x": data}, num_blocks=4)
                z = (tfs.block(df, "x") * 2.0).named("z")
                for _ in range(5):
                    out = tfs.map_blocks(z, df)
                    np.testing.assert_allclose(
                        out.column("z").values, data * 2.0
                    )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [
            threading.Thread(target=run_one, args=(t,)) for t in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors


class TestCheckNumerics:
    """config.check_numerics: the CheckNumerics role for every fetch
    without editing the graph — names the verb, block, and fetch."""

    def test_map_blocks_nan_raises(self):
        from tensorframes_tpu import dsl

        df = tfs.TensorFrame.from_dict(
            {"x": np.array([1.0, 0.0, 4.0])}, num_blocks=1
        )
        x = tfs.block(df, "x")
        z = (x / (x - x)).named("z")  # 0/0 -> nan
        with config.override(check_numerics=True):
            with pytest.raises(FloatingPointError, match="map_blocks.*'z'"):
                tfs.map_blocks(z, df)
        # off by default: same graph runs fine
        out = tfs.map_blocks(z, df)
        assert np.isnan(np.asarray(out.column("z").values)[1])

    def test_reduce_blocks_inf_raises(self):
        from tensorframes_tpu import dsl

        df = tfs.TensorFrame.from_dict({"x": np.array([1e308, 1e308])})
        s = dsl.reduce_sum(
            tfs.block(df, "x", tf_name="x_input"), axes=[0]
        ).named("x")
        with config.override(check_numerics=True):
            with pytest.raises(FloatingPointError, match="reduce_blocks"):
                tfs.reduce_blocks(s, df)

    def test_integer_outputs_ignored(self):
        df = tfs.TensorFrame.from_dict({"x": np.array([1, 2, 3])})
        with config.override(check_numerics=True):
            out = tfs.map_blocks(lambda x: {"z": x + 1}, df)
        assert out.column("z").values.tolist() == [2, 3, 4]


class TestExplainHlo:
    def test_stablehlo_text(self):
        from tensorframes_tpu import dsl

        df = tfs.TensorFrame.from_dict({"x": np.arange(8.0)})
        z = (tfs.block(df, "x") + 3.0).named("z")
        txt = tfs.explain_hlo(z, df)
        assert "stablehlo" in txt or "mhlo" in txt or "func" in txt
        assert "add" in txt

    def test_optimized_hlo_fuses(self):
        from tensorframes_tpu import dsl

        df = tfs.TensorFrame.from_dict({"x": np.arange(8.0)})
        x = tfs.block(df, "x")
        z = ((x + 1.0) * 2.0).named("z")
        txt = tfs.explain_hlo(z, df, optimized=True)
        assert "HloModule" in txt
