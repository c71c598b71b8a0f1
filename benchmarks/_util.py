"""Shared helpers for the benchmark suite.

The reference ships perf harnesses but keeps them all `ignore`d and never
records a number (`perf/ConvertPerformanceSuite.scala`,
`perf/ConvertBackPerformanceSuite.scala`, `perf/PerformanceSuite.scala` —
see SURVEY.md §6). This suite re-creates each of them as a real, runnable
benchmark that prints one JSON line per metric, the same wire format as
the repo-root `bench.py`.
"""

from __future__ import annotations

import json
import os
from typing import Optional

# Datasheet peaks per device kind (chip-level) — now owned by the
# runtime cost ledger (`runtime.costmodel.DEVICE_PEAKS`), which
# `tfs.diagnostics()` joins against; re-exported here LAZILY (PEP 562)
# so bench.py and older callers keep one import path without
# `import benchmarks._util` (scaled/emit users) paying the full
# framework import at module load.


def __getattr__(name):
    if name == "DEVICE_PEAKS":
        from tensorframes_tpu.runtime.costmodel import DEVICE_PEAKS

        return DEVICE_PEAKS
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def scaled(env: str, default: int) -> int:
    """Problem size, overridable via env (smaller on CPU smoke runs)."""
    return int(os.environ.get(env, default))


def run_block_mfu(batch: int, hidden: int, layers: int, iters: int) -> dict:
    """Compute-bound bf16 MFU harness (round-3 verdict weak #3), the ONE
    implementation shared by `benchmarks/mfu_bench.py` and the repo-root
    `bench.py` capture: block-level bf16 MLP through `map_blocks`, sized
    by the caller to saturate the MXU; MFU = XLA-counted flops x calls /
    wall / datasheet peak. Flops come from the runtime COST LEDGER
    (`runtime.costmodel`) — the warm-up dispatch already captured the
    exact compiled program's cost analysis, so this harness no longer
    re-lowers the graph (falls back to `api.cost_analysis` only when
    the ledger is disabled). The full-shape warm-up keeps compilation
    out of the timed region.

    Returns {achieved_flops_s, flops_per_call, mfu (None off-table),
    device_kind}."""
    import time

    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    import tensorframes_tpu as tfs
    from tensorframes_tpu import config as tfs_config
    from tensorframes_tpu import dsl
    from tensorframes_tpu.models import MLP
    from tensorframes_tpu.runtime import costmodel

    model = MLP([hidden] * (layers + 1), seed=0, param_dtype=jnp.bfloat16)
    graph = model.scoring_graph("features", block=True)
    data = np.random.RandomState(0).rand(batch, hidden).astype(
        ml_dtypes.bfloat16
    )
    df = tfs.TensorFrame.from_dict({"features": data}).to_device()
    with tfs_config.override(matmul_precision="default"):
        jax.block_until_ready(
            tfs.map_blocks(graph, df, trim=True).column("probs").values
        )
        entry = costmodel.program_costs().get(
            dsl.build(graph)[0].fingerprint()
        )
        flops_per_call = entry["flops_per_exec"] if entry else None
        if flops_per_call is None:
            # ledger off (TFS_COST_LEDGER=0) or capture unavailable:
            # pay the one-off re-lowering the ledger normally replaces
            from tensorframes_tpu.api import cost_analysis

            flops_per_call = cost_analysis(graph, df)["flops"]
        t0 = time.perf_counter()
        for _ in range(iters):
            out = tfs.map_blocks(graph, df, trim=True)
        jax.block_until_ready(out.column("probs").values)
        dt = time.perf_counter() - t0
    achieved = flops_per_call * iters / dt
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", dev.platform)
    peak = costmodel.DEVICE_PEAKS.get(kind, {}).get("matmul_flops_s")
    return {
        "achieved_flops_s": achieved,
        "flops_per_call": flops_per_call,
        "mfu": (achieved / peak) if peak else None,
        "device_kind": kind,
    }


def emit(metric: str, value: float, unit: str, baseline: Optional[float] = None):
    line = {
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": (value / baseline) if baseline else None,
    }
    print(json.dumps(line))
    return line


def freeze_keras_model(ctor_name: str, input_hw: int):
    """Build a PRODUCTION Keras architecture (`tf.keras.applications.
    <ctor_name>`) and freeze it with TF2's
    `convert_variables_to_constants_v2` — the modern form of the
    reference demo's freeze (`read_image.py:111-124`). The multi-MB
    graphs are shaped entirely by Keras, not by this repo. Weights are
    seeded-random: the environment has zero egress and no cached
    pretrained checkpoints, so `weights="imagenet"` cannot be
    satisfied — prediction agreement vs a TF session is checked instead
    (`tests/test_foreign_graphdef.py`), which is weight-independent
    evidence of correct ingestion/lowering.

    The ONE freeze recipe, shared by the BASELINE-config-5 benchmark
    and every model-zoo conformance test, so the graph measured is
    byte-identical to the graph validated. Requires TensorFlow (an
    optional tool here, never a runtime dep); raises ImportError where
    it is absent.

    Returns (graph_bytes, input_node, output_node, tf_score_fn)."""
    os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    import tensorflow as tf

    tf.keras.utils.set_random_seed(7)
    model = getattr(tf.keras.applications, ctor_name)(
        weights=None, input_shape=(input_hw, input_hw, 3)
    )
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2,
    )

    fn = tf.function(lambda x: model(x, training=False))
    cf = fn.get_concrete_function(
        tf.TensorSpec([None, input_hw, input_hw, 3], tf.float32)
    )
    frozen = convert_variables_to_constants_v2(cf)
    gd = frozen.graph.as_graph_def()

    def score(images):
        out = frozen(tf.constant(images))
        if isinstance(out, (list, tuple)):
            out = out[0]
        return out.numpy()

    return (
        gd.SerializeToString(),
        frozen.inputs[0].name.split(":")[0],
        frozen.outputs[0].name.split(":")[0],
        score,
    )


def freeze_keras_inception_v3(input_hw: int):
    """BASELINE config 5's model, through the shared recipe."""
    return freeze_keras_model("InceptionV3", input_hw)
