"""Run the full benchmark suite; one JSON line per metric on stdout.

Mirrors SURVEY.md §6's table: every harness the reference left `ignore`d
is a live benchmark here. `BENCH_SMOKE=1` shrinks every size for a quick
CI pass.
"""

from __future__ import annotations

import os
import runpy
import sys

SMOKE_SIZES = {
    "CONVERT_CELLS": "200000",
    "MAPSUM_ROWS": "200000",
    "MAPSUM_ITERS": "3",
    "KMEANS_ROWS": "5000",
    "KMEANS_DIM": "16",
    "KMEANS_ITERS": "3",
    "MLPROWS_ROWS": "20000",
    "MFU_BATCH": "256",
    "MFU_HIDDEN": "256",
    "MFU_LAYERS": "2",
    "MFU_ITERS": "3",
    "AGG_ROWS": "100000",
    "INCEPTION_IMAGES": "16",
    "INCEPTION_SIZE": "32",
    "INCEPTION_WIDTH": "8",
    "INCEPTIONV3_IMAGES": "4",
    "INCEPTIONV3_SIZE": "75",
    "RAGGED_ROWS": "20000",
    "TRAIN_DMODEL": "64",
    "TRAIN_LAYERS": "2",
    "TRAIN_SEQ": "32",
    "TRAIN_BATCH": "2",
    "TRAIN_STEPS": "3",
    "RAGGED_LOOP_ROWS": "500",
    "OVERLAP_CHUNK_ROWS": "200000",
    "OVERLAP_CHUNKS": "6",
    "OVERLAP_THROTTLE_MS": "20",
    "PIPE_ROWS": "100000",
    "PIPE_BLOCKS": "4",
    "PIPE_ITERS": "3",
    "TELE_ROWS": "100000",
    "TELE_BLOCKS": "4",
    "TELE_ITERS": "3",
    "FUSE_ROWS": "100000",
    "FUSE_BLOCKS": "4",
    "FUSE_ITERS": "3",
    # bucketing smoke keeps the REQUIRED 64 distinct block sizes (the
    # compile-count contract is about size cardinality, not row volume)
    # but shrinks every block to a handful of rows
    "BUCKET_BLOCKS": "64",
    "BUCKET_BASE": "5",
    "BUCKET_STEP": "3",
    "BUCKET_ITERS": "1",
    "SCHED_ROWS": "200000",
    "SCHED_BLOCKS": "8",
    "SCHED_ITERS": "2",
    "SCHED_CHAIN": "16",
    "CHAOS_ROWS": "100000",
    "CHAOS_BLOCKS": "8",
    "INGEST_SHARDS": "4",
    "INGEST_GROUPS": "2",
    "INGEST_GROUP_ROWS": "20000",
    "INGEST_ITERS": "2",
    "PLANPIPE_SHARDS": "4",
    "PLANPIPE_GROUPS": "2",
    "PLANPIPE_GROUP_ROWS": "20000",
    "PLANPIPE_ITERS": "2",
    # cache smoke keeps the DEEP-CHAIN geometry (the hit-vs-recompute
    # contract is about compute depth, not row volume) and trims rows
    "PLANPIPE_CACHE_ROWS": "100000",
    "PLANPIPE_CACHE_DEPTH": "24",
    # relational smoke keeps MANY ROW GROUPS per shard (the pushdown
    # contract is about group-granular pruning, not row volume)
    "REL_SHARDS": "4",
    "REL_GROUPS": "8",
    "REL_GROUP_ROWS": "10000",
    "REL_ITERS": "2",
    "OVERLOAD_ROWS": "100000",
    "OVERLOAD_BLOCKS": "4",
    "OVERLOAD_CALLS": "6",
    "OVERLOAD_STORM": "3",
    "BLACKBOX_ROWS": "100000",
    "BLACKBOX_BLOCKS": "4",
    "BLACKBOX_ITERS": "6",
    "BLACKBOX_STORM": "3",
    "SERVE_ROWS": "512",
    "SERVE_CALLS": "24",
    "SERVE_CLIENTS": "4",
    # autotune smoke keeps the ADVERSARIAL geometry (block sizes just
    # above a growth-2 rung — the pad-waste contract is about where the
    # cluster sits, not row volume) and trims block count/cells/iters
    "AUTOTUNE_BLOCKS": "12",
    "AUTOTUNE_CELLS": "8",
    "AUTOTUNE_ITERS": "2",
    "AUTOTUNE_GROUP_ROWS": "2000",
    "AUTOTUNE_STREAM_ITERS": "2",
    "AUTOTUNE_DECODE_MS": "15",
    "CKPT_SHARDS": "4",
    "CKPT_GROUPS": "2",
    "CKPT_GROUP_ROWS": "20000",
    "CKPT_ITERS": "2",
    "CKPT_EVERY": "2",
    # globalframe smoke keeps the MANY-BLOCKS geometry (the dispatch-
    # bound regime the one-SPMD-program claim is about) and trims rows
    "GLOBAL_ROWS": "100000",
    "GLOBAL_BLOCKS": "32",
    "GLOBAL_ITERS": "3",
    "GLOBAL_CHAIN": "8",
    # autobatch smoke keeps MANY DISTINCT block sizes (the compile-
    # cardinality contract, like the bucketing smoke) and tiny blocks
    "AUTOBATCH_BLOCKS": "12",
    "AUTOBATCH_BASE": "5",
    "AUTOBATCH_STEP": "3",
    "AUTOBATCH_ITERS": "2",
}


def main():
    if os.environ.get("BENCH_SMOKE"):
        for k, v in SMOKE_SIZES.items():
            os.environ.setdefault(k, v)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(here))
    from tensorframes_tpu import config

    config.enable_compilation_cache()
    for mod in (
        "convert_bench",
        "pipeline_bench",
        "telemetry_bench",
        "fusion_bench",
        "bucketing_bench",
        "map_sum_bench",
        "kmeans_bench",
        "map_rows_mlp_bench",
        "mfu_bench",
        "aggregate_bench",
        "inception_bench",
        "frozen_inception_v3_bench",
        "ragged_map_rows_bench",
        "stream_overlap_bench",
        "ingest_bench",
        "plan_pipeline_bench",
        "relational_bench",
        "checkpoint_bench",
        "overload_bench",
        "blackbox_bench",
        "serving_bench",
        "autotune_bench",
        # LAST FIVE: on a 1-CPU-device host these retarget the process
        # to a virtual 8-device mesh (clear_backends), which must not
        # leak into any bench that runs before them
        "autobatch_bench",
        "globalframe_bench",
        "scheduler_bench",
        "chaos_bench",
        "train_bench",
    ):
        runpy.run_path(os.path.join(here, f"{mod}.py"), run_name="__main__")
    _save_profile()


def _save_profile():
    """Emit the run's workload profile alongside the BENCH JSON lines:
    every bench run leaves a durable `WorkloadProfile` artifact
    (programs/rungs, bucket fill, verb latencies, cost-model
    residuals) that `tools/profile_report.py` renders/diffs offline —
    the cross-run evidence the autotuning ROADMAP item consumes.
    BENCH_PROFILE overrides the path; "0"/"off" disables. Never fails
    the bench run."""
    path = os.environ.get("BENCH_PROFILE", "bench_profile.json")
    if not path or path.lower() in ("0", "off", "none"):
        return
    try:
        from tensorframes_tpu.runtime import profiler

        profiler.snapshot(note="benchmarks/run_all").save(path)
        print(f"PROFILE_ARTIFACT {path}")
    except Exception as e:  # the artifact must never fail the bench
        print(f"PROFILE_ARTIFACT error {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
