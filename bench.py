"""Benchmark: prints ONE JSON line with the headline metric.

Headline (BASELINE.md primary): `map_blocks` rows/sec/chip on the README
"x+3" graph — end-to-end through the public API on the TPU this process
owns. No TPU → non-zero exit and no line: a CPU number is never printed
under a device metric's name. The JSON line also carries the
hardware-bound views the raw rows/s hides:

- ``hbm_frac``: achieved HBM traffic of the x+3 chain as a fraction of
  the chip's peak bandwidth (elementwise maps are bandwidth-bound at
  best; this is the honest utilization number);
- ``mlp_mfu``: model-FLOP utilization of a matmul-heavy `map_rows` MLP
  (BASELINE config 3) against the chip's peak matmul FLOP/s.

A device kind missing from `runtime.costmodel.DEVICE_PEAKS` leaves the
peak-relative fields null (unknown, never a default).

Timing invariant: verbs dispatch asynchronously and return device
arrays, so EVERY timed region here must end with
``jax.block_until_ready`` (or an equivalent materializing
``np.asarray``) on the region's outputs — a region without one times
only the enqueue and reports a fake speedup.
``benchmarks/pipeline_bench.py`` additionally asserts the chained
map->reduce path performs zero host syncs.
"""

import json
import os
import sys
import time

import numpy as np

# Datasheet peaks per device kind: the one shared table
# (benchmarks/_util.DEVICE_PEAKS), so bench.py and the benchmark suite
# can never disagree on a chip's peak.
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchmarks._util import DEVICE_PEAKS as _PEAKS  # noqa: E402


def _bench_x3_chain(tfs, jax, n: int, iters: int):
    """Chained x+3 maps on a device-resident frame; returns rows/s."""
    from tensorframes_tpu.frame import Column

    df = tfs.TensorFrame.from_dict(
        {"x": np.arange(n, dtype=np.float32)},
        num_blocks=int(os.environ.get("BENCH_BLOCKS", 1)),
    ).to_device()
    x = tfs.block(df, "x")
    z = (x + 3.0).named("z")

    out = tfs.map_blocks(z, df)  # warm-up: compile + first execution
    assert float(np.asarray(out["z"].values[1])) == 4.0

    # Steady state: each iteration's output feeds the next map; dispatch
    # is async so chained device work pipelines; one sync at the end.
    t0 = time.perf_counter()
    cur = df
    for _ in range(iters):
        out = tfs.map_blocks(z, cur)
        cur = tfs.TensorFrame([Column("x", out["z"].values)])
    jax.block_until_ready(cur["x"].values)
    t1 = time.perf_counter()
    assert float(np.asarray(cur["x"].values[1])) == 1.0 + 3.0 * iters
    return n * iters / (t1 - t0)


def _bench_mlp_mfu(tfs, jax, peak_flops):
    """BASELINE config 3: matmul-heavy map_rows MLP; returns
    (rows/s, mfu or None)."""
    from tensorframes_tpu import config as tfs_config
    from tensorframes_tpu.api import cost_analysis
    from tensorframes_tpu.models import MLP

    rows = int(os.environ.get("BENCH_MLP_ROWS", 1_000_000))
    dim = int(os.environ.get("BENCH_MLP_DIM", 512))
    rng = np.random.RandomState(0)
    data = rng.rand(rows, dim).astype(np.float32)
    df = tfs.TensorFrame.from_dict({"features": data}).to_device()

    model = MLP([dim, dim, dim, 10], seed=0)
    graph = model.scoring_graph("features", block=False)

    with tfs_config.override(matmul_precision="default"):  # MXU bf16 passes
        warm = tfs.TensorFrame.from_dict({"features": data[:1024]})
        ca = cost_analysis(
            model.scoring_graph("features", block=True), warm
        )
        flops_per_row = ca["flops_per_row"]

        # warm at the FULL shape: jit specializes per shape, so a
        # small-frame warm-up would leave the 1M-row compile inside the
        # timed region (it dominated the round-3 first capture)
        jax.block_until_ready(
            tfs.map_rows(graph, df).column("probs").values
        )
        t0 = time.perf_counter()
        out = tfs.map_rows(graph, df)
        jax.block_until_ready(out.column("probs").values)
        dt = time.perf_counter() - t0
    rows_s = rows / dt
    mfu = (rows_s * flops_per_row / peak_flops) if peak_flops else None
    return rows_s, mfu


def _bench_block_mfu():
    """Compute-bound flagship (round-3 verdict weak #3): the shared
    `benchmarks/_util.run_block_mfu` harness — one implementation, so
    this capture and the suite's mfu_bench cannot diverge. Returns
    (achieved model FLOP/s, mfu|None)."""
    from benchmarks._util import run_block_mfu

    batch = int(os.environ.get("BENCH_MFU_BATCH", 8192))
    hidden = int(os.environ.get("BENCH_MFU_HIDDEN", 4096))
    layers = int(os.environ.get("BENCH_MFU_LAYERS", 8))
    iters = int(os.environ.get("BENCH_MFU_ITERS", 20))
    r = run_block_mfu(batch, hidden, layers, iters)
    return r["achieved_flops_s"], r["mfu"]


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"bench.py needs a TPU; jax found {dev.platform!r} "
            f"({dev.device_kind}). Nothing measured.",
            file=sys.stderr,
        )
        return 1

    import tensorframes_tpu as tfs

    tfs.config.enable_compilation_cache()
    # a kind missing from the table is an unknown peak, never a default
    peaks = _PEAKS.get(dev.device_kind, {})

    n = int(os.environ.get("BENCH_ROWS", 200_000_000))
    # enough chained iterations that per-dispatch overhead amortizes out
    # of the steady-state rate (each iteration is ~10ms of device work;
    # 30 of them keep the whole chain under a second)
    iters = int(os.environ.get("BENCH_ITERS", 30))

    rows_per_sec = _bench_x3_chain(tfs, jax, n, iters)
    # x+3 moves one f32 read + one f32 write per row per iteration
    bytes_s = rows_per_sec * 2 * 4
    hbm_frac = (
        round(bytes_s / peaks["hbm_bytes_s"], 4)
        if peaks.get("hbm_bytes_s")
        else None
    )

    mlp_rows_s, mfu = _bench_mlp_mfu(
        tfs, jax, peaks.get("matmul_flops_s")
    )

    block_flops_s, block_mfu = _bench_block_mfu()

    print(
        json.dumps(
            {
                "metric": f"map_blocks x+3 rows/sec/chip (tpu, {n} rows)",
                "value": round(rows_per_sec),
                "unit": "rows/s",
                "hbm_frac": hbm_frac,
                "hbm_peak_bytes_s": peaks.get("hbm_bytes_s"),
                "mlp_rows_per_s": round(mlp_rows_s),
                "mlp_mfu": round(mfu, 4) if mfu is not None else None,
                # compute-bound flagship: block-level bf16 MLP (the
                # per-row mlp_mfu above is BASELINE config 3 and is
                # dispatch-bound by design; this row shows the MXU)
                "block_bf16_flops_s": round(block_flops_s),
                "block_bf16_mfu": (
                    round(block_mfu, 4) if block_mfu is not None else None
                ),
                "mfu_peak_flops_s": peaks.get("matmul_flops_s"),
                "device_kind": dev.device_kind,
                "device_count": len(jax.devices()),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
