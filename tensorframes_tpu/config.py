"""Config layer: the knobs the reference hardcoded or lacked entirely.

SURVEY.md §5 flags the reference's config story as "essentially none"
(hardcoded UDAF buffer size, graph-to-file flag) and calls for a real
layer: mesh shape, dtype policy, block sizing, compilation cache. This
module is that layer — a process-global `Config` with scoped overrides::

    tfs.config.update(matmul_precision="default")   # fast MXU bf16 passes
    with tfs.config.override(default_num_blocks=16):
        ...

Knobs:
- ``matmul_precision``: "highest" (default — numerical parity with the
  reference's fp32 TF kernels) | "default" (MXU-native bf16 passes) |
  "tensorfloat32". Consumed by the MatMul/Conv lowerings.
- ``default_num_blocks``: blocks for frames built without an explicit
  partitioning (None = single block).
- ``default_mesh``: mesh used by verbs when ``mesh=`` is omitted
  (None = single device).
- `enable_compilation_cache()` (a call, not a knob): turns on JAX's
  persistent compilation cache at a place the deployment controls
  (survives process restarts — the reference re-imported its graph into
  a fresh TF session per task, `DebugRowOps.scala:790`).
- ``aggregate_buffer_rows``: host-side group batching threshold (the
  reference's hardcoded ``bufferSize=10``, `DebugRowOps.scala:580`).

Pin tracking (the autotuner's "never fight a pin" substrate): every
knob set EXPLICITLY — through `update()`, inside an `override()` scope,
or seeded from a well-formed ``TFS_*`` env var at import — is recorded
as *pinned* (`explicit_keys()` / `is_explicit()`). The closed-loop
autotuner (`runtime.autotune`) writes knobs only through `set_tuned()`,
which refuses pinned keys, so an operator's explicit setting always
wins over a tuned one; `tuned()` reports what the tuner currently owns
and `reset_tuning()` restores those knobs to their (env-seeded)
defaults. A later `update()` of a tuned knob converts it to a pin.

Env parsing: every ``TFS_*`` scalar override reads through the
malformed-env-falls-back-to-default helpers below — a typo'd value
must never break the package import (the histogram_buckets JSON knob
established the convention); a malformed value is ignored entirely
(default value, no pin).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

__all__ = [
    "Config",
    "get",
    "update",
    "override",
    "explicit_keys",
    "is_explicit",
    "set_tuned",
    "tuned",
    "default_value",
    "reset_tuning",
    "enable_compilation_cache",
]


# fields whose env var was present AND parsed cleanly during Config
# construction — the import-time pin seed (a malformed value falls back
# to the default and pins nothing). Populated by the _env_* helpers;
# re-running a default_factory (e.g. `Config()` inside default_value)
# only re-adds the same names, so the set is stable.
_ENV_SEEDED: set = set()


def _env_bool(var: str, default: bool, field: str) -> bool:
    import os

    raw = os.environ.get(var)
    if raw is None or raw == "":
        return default
    _ENV_SEEDED.add(field)
    return raw.lower() not in ("0", "false", "off")


def _env_int(var: str, default: int, field: str,
             minimum: Optional[int] = None) -> int:
    import os

    raw = os.environ.get(var)
    if raw is None or raw == "":
        return default
    try:
        v = int(raw)
    except (TypeError, ValueError):
        return default  # malformed env never breaks the import
    _ENV_SEEDED.add(field)
    return v if minimum is None else max(minimum, v)


def _env_float(var: str, default: float, field: str,
               minimum: Optional[float] = None) -> float:
    import os

    raw = os.environ.get(var)
    if raw is None or raw == "":
        return default
    try:
        v = float(raw)
    except (TypeError, ValueError):
        return default  # malformed env never breaks the import
    _ENV_SEEDED.add(field)
    return v if minimum is None else max(minimum, v)


def _env_str(var: str, default: str, field: str,
             mapping: Optional[dict] = None,
             choices: Optional[tuple] = None) -> str:
    import os

    raw = os.environ.get(var)
    if raw is None or raw == "":
        return default
    low = raw.lower()
    val = mapping.get(low, low) if mapping is not None else raw
    if choices is not None and val not in choices:
        return default  # an out-of-vocabulary value is malformed:
        # default value, no pin — same contract as a typo'd number
    _ENV_SEEDED.add(field)
    return val


def _env_histogram_buckets():
    """Seed ``histogram_buckets`` from TFS_HISTOGRAM_BUCKETS (a JSON
    dict: family or metric name -> ascending boundary list). Malformed
    JSON must never break the package import — it reads as None (the
    built-in defaults) and the bad value is simply ignored."""
    import json
    import os

    raw = os.environ.get("TFS_HISTOGRAM_BUCKETS", "")
    if not raw:
        return None
    try:
        val = json.loads(raw)
        if isinstance(val, dict):
            _ENV_SEEDED.add("histogram_buckets")
            return val
        return None
    except Exception:
        return None


@dataclasses.dataclass
class Config:
    # Every SCALAR knob seeds from TFS_<KNOB> through the _env_*
    # helpers (tfslint TFS003 enforces the parity): a deployment tunes
    # any of them without a code change, a well-formed value pins the
    # knob against the autotuner, and a malformed value falls back to
    # the default without breaking the import.
    matmul_precision: str = dataclasses.field(
        default_factory=lambda: _env_str(
            "TFS_MATMUL_PRECISION", "highest", "matmul_precision",
            mapping={}, choices=("highest", "default", "tensorfloat32"),
        )
    )
    default_num_blocks: Optional[int] = None
    default_mesh: Optional[object] = None
    aggregate_buffer_rows: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_AGGREGATE_BUFFER_ROWS", 10, "aggregate_buffer_rows",
            minimum=1,
        )
    )
    # aggregate: above this many DISTINCT group sizes, graphs classified
    # as Reduce(rowwise(placeholder), axis=0) (api._chunk_combiners:
    # Sum/Min/Max/Prod, float Mean) switch from the exact
    # one-vmap-per-size plan to pow2 chunk decomposition with a
    # derived-monoid combine — compiles O(log max_size) instead of
    # O(#distinct sizes). Unclassifiable graphs always stay on the exact
    # plan (correct, but compile-heavy under pathological distributions).
    aggregate_exact_size_limit: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_AGGREGATE_EXACT_SIZE_LIMIT", 32,
            "aggregate_exact_size_limit", minimum=0,
        )
    )
    # aggregate: sort-free fast path for classified monoid graphs — the
    # rowwise transform runs over ALL rows in one XLA call and one
    # device segment_<op> per fetch replaces the argsort + per-size
    # plans entirely (host argsort dominated keyed aggregation at the
    # 10M-row TPU benchmark scale). Accumulation order differs from the
    # exact whole-group plan (FP reassociation). Off = exact/chunk plans.
    aggregate_segment_fast: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_AGGREGATE_SEGMENT_FAST", True, "aggregate_segment_fast"
        )
    )
    # aggregate: float Sum/Mean segment tables with at most this many
    # DISTINCT KEYS compute as a one-hot matmul on the MXU instead of
    # XLA's scatter-add lowering of segment_sum (scatter serializes on
    # TPU; a (rows x keys) @ (rows x cell) matmul does not). None =
    # auto: 256 on TPU, 0 elsewhere — on CPU/GPU scatter-add is fast
    # and the matmul's extra FLOPs only cost (measured ~28x slower on
    # CPU). Set an int to force either way.
    aggregate_onehot_keys: Optional[int] = None
    # Executor compile-cache bound (LRU): long-lived services whose
    # graphs / shapes drift would otherwise accumulate compiled
    # executables forever (the cache is never cleared implicitly).
    executor_cache_entries: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_EXECUTOR_CACHE_ENTRIES", 512, "executor_cache_entries",
            minimum=1,
        )
    )
    # Shape-bucketed block execution (`shape_policy`): pad every block
    # feed up to a geometric row-bucket ladder and mask/slice the pad
    # rows, so a workload with arbitrary drifting block sizes compiles
    # O(log max-block-rows) XLA programs per graph instead of one per
    # distinct size. Applies only to dispatches proven safe (row-local
    # map graphs; monoid-classified reduces); everything else runs the
    # exact unbucketed program regardless of this knob. Float sum/mean
    # under bucketing reduce over a padded axis, so XLA may reassociate
    # the accumulation (the same tolerance as stacking block partials);
    # turn this off when exact FP accumulation order outweighs bounded
    # compile counts. Env override TFS_SHAPE_BUCKETING ("0" disables)
    # seeds the initial value, mirroring TFS_NATIVE_EXECUTOR.
    shape_bucketing: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_SHAPE_BUCKETING", True, "shape_bucketing"
        )
    )
    # Bucket-ladder geometry: rung k holds min * growth^k rows. Growth
    # trades pad waste (worst-case (growth-1)/growth of a block) against
    # ladder length (compile count ~ log_growth(max rows)).
    shape_bucket_growth: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_SHAPE_BUCKET_GROWTH", 2.0, "shape_bucket_growth",
            # the ladder needs growth > 1 to be finite; 1.05 is the
            # autotuner's own SAFETY_BOUNDS floor
            minimum=1.05,
        )
    )
    shape_bucket_min: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_SHAPE_BUCKET_MIN", 8, "shape_bucket_min", minimum=1
        )
    )
    # Multi-device block scheduler (`runtime.scheduler`): non-mesh verbs
    # spread per-block dispatches across jax.local_devices() (size-aware
    # largest-first placement; feeds are device_put onto the assigned
    # device and jit's committed-input semantics place the execution).
    # Values:
    #   "auto" — (default) schedule when >1 local device exists
    #   "on"   — schedule onto all local devices even when there is one
    #            (forces the scheduled code path — explicit device_put,
    #            per-device ledgers)
    #   "off"  — every dispatch lands on the default device (the
    #            pre-scheduler behavior)
    #   "global" — route eligible verbs through `GlobalFrame` SPMD
    #            dispatch (`globalframe.py`): columns become single
    #            jax.Arrays sharded over a data mesh, one compiled
    #            program spans every device, classified reduces lower
    #            to in-program collectives. Ineligible dispatches
    #            (non-row-local maps, unclassified reduces, ragged/
    #            string feeds, frames below global_frame_min_rows)
    #            fall back to per-block scheduling exactly as "auto".
    # mesh= always takes precedence, and the native executor is never
    # scheduled (it owns its own PJRT host). Per-call override: the
    # devices= parameter on every non-mesh verb. Under scheduling, jit
    # specializes each program per device it touches, so compile counts
    # are bounded by ndev x the single-device count (ndev x ladder rungs
    # under shape_bucketing). Reduce combines stay bit-identical for
    # min/max and within the documented reassociation tolerance for
    # float sum/mean. Env override TFS_BLOCK_SCHEDULER seeds the initial
    # value, mirroring TFS_SHAPE_BUCKETING.
    block_scheduler: str = dataclasses.field(
        default_factory=lambda: _env_str(
            "TFS_BLOCK_SCHEDULER", "auto", "block_scheduler",
            mapping={
                "0": "off", "false": "off", "1": "on", "true": "on",
            },
        )
    )
    # Global sharded frames (`globalframe.py`): a frame routed through
    # the GlobalFrame SPMD path (block_scheduler="global", or the
    # auto-route on eligible verbs) must carry at least this many rows;
    # below it the per-shard work would be dominated by sharded
    # device_put + collective latency and the verb falls back to
    # per-block scheduling. Tunable by the closed-loop autotuner (an
    # explicit update()/override()/env set pins it like every knob).
    # Env override TFS_GLOBAL_FRAME_MIN_ROWS seeds the initial value.
    global_frame_min_rows: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_GLOBAL_FRAME_MIN_ROWS", 2048, "global_frame_min_rows",
            minimum=0,
        )
    )
    # Auto-batched per-row control flow (`graph/vectorize.py`): graphs
    # containing functionalized `_Cond`/`_While` whose branch/body
    # subgraphs are row-local classify as row-local themselves and lower
    # to masked dense programs (cond -> both-branches + select on the
    # batched predicate, while -> convergence-masked fixed point), so
    # branchy per-row graphs ride the bucket ladder, serving batcher and
    # the GlobalFrame one-dispatch SPMD path instead of falling back to
    # unbatched execution. Off = the historical conservative classifier
    # (any control-flow node disqualifies the graph) and scalar-pred-only
    # lowering. Env override TFS_ROW_VECTORIZE ("0" disables) seeds the
    # initial value.
    row_vectorize: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_ROW_VECTORIZE", True, "row_vectorize"
        )
    )
    # Pipelined ingest (`ingest.pipeline`): stream verbs and the io
    # readers run shard discovery -> parallel decode -> H2D transfer ->
    # compute as concurrently-executing stages over bounded queues.
    # Off = stage-serial: the SAME stage functions run inline on the
    # consumer thread (no overlap) — the stage-serial reference
    # tests/test_ingest.py compares the pipeline's results against, and
    # an escape hatch for single-core hosts where pipeline threads only
    # add overhead.
    # Env override TFS_INGEST_PIPELINE ("0" disables) seeds the initial
    # value, mirroring TFS_SHAPE_BUCKETING.
    ingest_pipeline: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_INGEST_PIPELINE", True, "ingest_pipeline"
        )
    )
    # Delivery-queue bound of the ingest pipeline (was the hard-coded
    # depth=1 of `_prefetch_iter`): how many decoded chunks may sit
    # ready ahead of the consumer. Peak buffered chunks for the
    # canonical discovery -> decode(W) -> transfer chain is
    # W + 2*depth + 4 (see ingest/pipeline.py's bound derivation —
    # asserted in tests/test_ingest.py), so host memory for a stream
    # is ~that many chunks regardless of stream length. Raise it when
    # chunk decode time is bursty; lower it when chunks are huge. Env
    # override TFS_STREAM_PREFETCH_DEPTH seeds the initial value.
    stream_prefetch_depth: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_STREAM_PREFETCH_DEPTH", 1, "stream_prefetch_depth",
            minimum=1,
        )
    )
    # Durable-stream commit cadence (`runtime.checkpoint`): a streaming
    # reduce given checkpoint= without an explicit checkpoint_every=
    # atomically commits its manifest + partial table after this many
    # FOLDED chunks (empty chunks advance the watermark but do not
    # count as folds). Lower = tighter recovery point, more fsyncs.
    # Env override TFS_STREAM_CHECKPOINT_EVERY seeds the initial value.
    stream_checkpoint_every: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_STREAM_CHECKPOINT_EVERY", 16, "stream_checkpoint_every",
            minimum=1,
        )
    )
    # Pipelined plan execution (`lazy.force` over the stage-graph
    # runtime from `ingest.pipeline`): block feed-prep (slice + pad +
    # device staging) for block k+1 runs on a pipeline stage while the
    # consumer thread dispatches block k, so H2D transfer overlaps
    # compute across the plan's blocks. Off = the historical
    # block-serial loop (prep and dispatch interleaved on one thread) —
    # the reference tests/test_materialize.py compares the pipelined
    # loop's results against, and the single-core escape hatch. Env
    # override TFS_PLAN_PIPELINE ("0" disables) seeds the initial value.
    plan_pipeline: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_PLAN_PIPELINE", True, "plan_pipeline"
        )
    )
    # Delivery-queue bound of the plan pipeline: how many prepared
    # blocks may sit ready ahead of the dispatching consumer. The
    # prep stage holds at most depth+2 blocks' feeds beyond the
    # in-flight dispatch (the ingest pipeline's W + 2*depth + 4 queue
    # bound with W=1), so peak extra host memory is ~that many blocks.
    # Env override TFS_PLAN_PIPELINE_DEPTH seeds the initial value.
    plan_pipeline_depth: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_PLAN_PIPELINE_DEPTH", 2, "plan_pipeline_depth",
            minimum=1,
        )
    )
    # Relational plan optimizer (`graph.optimizer`): rewrite the plan
    # DAG built by filter/select/group_by/sort_by/join before
    # execution — common-subplan dedup, filter-below-map reordering,
    # predicate pushdown into the ingest scan, column pruning, and
    # map fusion across relational boundaries. Every rewrite is priced
    # against the cost ledger's residuals-corrected throughput and
    # accepted only when the modeled plan cost strictly drops; off =
    # execute the verbs exactly as written (the reference
    # tests/test_plan.py compares the rewritten plan's results and
    # decode counts against). Env override TFS_PLAN_OPTIMIZER ("0"
    # disables) seeds the initial value.
    plan_optimizer: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_PLAN_OPTIMIZER", True, "plan_optimizer"
        )
    )
    # Default filter selectivity the plan optimizer assumes when a
    # `filter(...)` carries no explicit selectivity= hint: the modeled
    # fraction of rows that survive the predicate. Feeds the cost
    # estimates in tfs.explain() and the accept/reject pricing of
    # pushdown rewrites; it never affects results, only plan choice.
    # Env override TFS_PLAN_SELECTIVITY_DEFAULT seeds the initial
    # value.
    plan_selectivity_default: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_PLAN_SELECTIVITY_DEFAULT", 0.5,
            "plan_selectivity_default", minimum=0.0,
        )
    )
    # Materialization cache byte budget (`runtime.materialize`): total
    # on-disk bytes the content-keyed result cache may hold; LRU
    # entries evict to stay under it. 0 (the default) disables the
    # cache entirely — zero behavior change, no files written. Keys
    # are (data fingerprint, program fingerprint, config digest), so a
    # numerics-relevant knob change can never serve a stale result.
    # Env override TFS_MATERIALIZE_CACHE_BYTES seeds the initial value.
    materialize_cache_bytes: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_MATERIALIZE_CACHE_BYTES", 0, "materialize_cache_bytes",
            minimum=0,
        )
    )
    # Materialization cache directory: where `runtime.materialize`
    # commits its entries (atomic temp-file + os.replace, same
    # discipline as runtime.checkpoint). Empty (the default) = a
    # process-private temp directory created on first store (entries
    # die with the process); set a persistent path to share warm
    # results across processes. Env override TFS_MATERIALIZE_CACHE_DIR
    # seeds the initial value.
    materialize_cache_dir: str = dataclasses.field(
        default_factory=lambda: _env_str(
            "TFS_MATERIALIZE_CACHE_DIR", "", "materialize_cache_dir"
        )
    )
    # Decode thread-pool width for multi-file datasets
    # (`ingest.dataset.IngestStream`): 0 = auto (min(4, host cores)).
    # pyarrow releases the GIL inside Parquet/IPC decode, so workers
    # scale with real cores; each worker holds at most one chunk plus
    # the shared reorder window. Env override TFS_INGEST_DECODE_WORKERS
    # seeds the initial value.
    ingest_decode_workers: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_INGEST_DECODE_WORKERS", 0, "ingest_decode_workers"
        )
    )
    # One-time per-program warning when jit has compiled more than this
    # many distinct input shapes for a single cached program — the
    # recompile-storm signal `compile_count` (distinct lowered callables)
    # structurally cannot see. 0 disables the check.
    recompile_warn_shapes: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_RECOMPILE_WARN_SHAPES", 16, "recompile_warn_shapes",
            minimum=0,
        )
    )
    # Telemetry master switch (`utils.telemetry`): span recording,
    # histogram observation and jax TraceAnnotation mirroring for every
    # verb / plan stage / per-block dispatch / compile event. Off =
    # near-zero overhead (a span site costs one config read and a no-op
    # context); the legacy flat counters (`stats()`) stay live either
    # way. Env override TFS_TELEMETRY ("0" disables) seeds the initial
    # value, mirroring TFS_SHAPE_BUCKETING.
    telemetry: bool = dataclasses.field(
        default_factory=lambda: _env_bool("TFS_TELEMETRY", True, "telemetry")
    )
    # Span ring-buffer bound (`utils.telemetry`): a long-lived service
    # keeps the freshest N spans and counts what fell off — memory stays
    # O(N) no matter how long the process runs. Applied on
    # `telemetry.reset()` (the ring is rebuilt at the current value).
    telemetry_ring_entries: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_TELEMETRY_RING_ENTRIES", 8192, "telemetry_ring_entries",
            minimum=1,
        )
    )
    # Live telemetry endpoint (`utils.telemetry_http`): when non-zero,
    # `tfs.telemetry.serve()` (and the import-time auto-start) binds an
    # HTTP server on this port serving /metrics (Prometheus text),
    # /healthz (device-health JSON), /diagnostics (JSON) and /trace
    # (Chrome trace JSON). 0 (default) = off; `serve(port=0)` picks an
    # ephemeral port explicitly. Binds 127.0.0.1 unless
    # telemetry_host says otherwise — the endpoint exposes program
    # fingerprints and device state and has NO auth, so exposing it
    # beyond localhost is a deliberate operator decision. Env override
    # TFS_TELEMETRY_PORT seeds the initial value (set it and the
    # package import starts the server).
    telemetry_port: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_TELEMETRY_PORT", 0, "telemetry_port"
        )
    )
    telemetry_host: str = dataclasses.field(
        default_factory=lambda: _env_str(
            "TFS_TELEMETRY_HOST", "127.0.0.1", "telemetry_host"
        )
    )
    # Histogram bucket boundaries (`utils.telemetry`): override the
    # fixed per-family ladders by bucket FAMILY ("seconds" | "rows" |
    # "bytes" | "fraction") or by exact metric name ("verb_seconds" —
    # the name wins over its family). Value: ascending float list. The
    # built-in defaults are unchanged (exports stay byte-identical
    # until an operator opts in); a service whose latencies live in one
    # default bucket (ms-scale serving) sets e.g.
    # {"verb_seconds": [1e-4, 5e-4, 1e-3, ...]}. Applies to histogram
    # series CREATED after the change (existing series keep the ladder
    # they were born with — fixed buckets are what make concurrent
    # observation and merge well-defined); `telemetry.reset()` rebuilds
    # everything at the current value. Env override
    # TFS_HISTOGRAM_BUCKETS (JSON dict) seeds the initial value.
    histogram_buckets: Optional[dict] = dataclasses.field(
        default_factory=_env_histogram_buckets
    )
    # Flight-recorder master switch (`runtime.blackbox`): when True
    # (the default — the recorder is always armed), a typed fault
    # escaping the runtime (deadline, shed, eviction, OOM exhaustion,
    # checkpoint corruption, serving 5xx) captures an incident bundle.
    # Costs nothing fault-free: capture only runs on fault paths, and
    # disabling turns even those into one attribute read. Env override
    # TFS_INCIDENT_CAPTURE ("0" disables) seeds the initial value.
    incident_capture: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_INCIDENT_CAPTURE", True, "incident_capture"
        )
    )
    # Incident bundle directory (`runtime.blackbox`): where postmortem
    # bundles are committed (CheckpointStore atomic protocol). Empty
    # (the default) = a process-private temp directory created on first
    # capture (bundles die with the test/process); operators set a
    # persistent path so 3am evidence survives a restart. Env override
    # TFS_INCIDENT_DIR seeds the initial value.
    incident_dir: str = dataclasses.field(
        default_factory=lambda: _env_str(
            "TFS_INCIDENT_DIR", "", "incident_dir"
        )
    )
    # Trailing evidence window (`runtime.blackbox`), seconds: a bundle
    # keeps only span-ring events that overlap the last
    # incident_window_s before the fault, and stamps its metric deltas
    # with the age they actually cover. Env override
    # TFS_INCIDENT_WINDOW_S seeds the initial value.
    incident_window_s: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_INCIDENT_WINDOW_S", 60.0, "incident_window_s",
            minimum=0.0,
        )
    )
    # Incident store bundle-count budget (`runtime.blackbox`): the
    # least-recently-written bundles are pruned to keep at most this
    # many on disk. 0 = no count bound (bytes still bound the store).
    # Env override TFS_INCIDENT_MAX_BUNDLES seeds the initial value.
    incident_max_bundles: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_INCIDENT_MAX_BUNDLES", 32, "incident_max_bundles",
            minimum=0,
        )
    )
    # Incident store byte budget (`runtime.blackbox`): total on-disk
    # bundle bytes; LRU bundles prune to stay under it, and a capture
    # whose payload cannot fit at all degrades to a counted
    # incidents_suppressed{reason="store"} — 0 is a real zero-byte
    # quota (every capture suppresses; the ENOSPC degradation path),
    # not "unlimited". Env override TFS_INCIDENT_MAX_BYTES seeds the
    # initial value.
    incident_max_bytes: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_INCIDENT_MAX_BYTES", 67108864, "incident_max_bytes",
            minimum=0,
        )
    )
    # Per-fingerprint incident rate limit (`runtime.blackbox`),
    # seconds: a repeat of the same incident fingerprint (trigger x
    # program x fault class) within this window increments
    # incidents_suppressed{reason="rate_limit"} instead of writing —
    # a shed storm leaves ONE bundle plus a count. 0 disables
    # dedup (every capture writes). Env override
    # TFS_INCIDENT_RATE_LIMIT_S seeds the initial value.
    incident_rate_limit_s: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_INCIDENT_RATE_LIMIT_S", 30.0, "incident_rate_limit_s",
            minimum=0.0,
        )
    )
    # Cost-model accuracy warning threshold (`runtime.costmodel
    # .residuals`): a program whose span-achieved time per dispatch is
    # more than this factor away (either direction) from the cost
    # model's prediction is flagged in the diagnostics "cost-model
    # accuracy" section and in saved workload profiles. The residual is
    # RELATIVE — predictions use a per-process effective throughput
    # fitted over every attributed program, so a flag means "the model
    # misprices this program vs its peers", which is exactly what a
    # cost-based planner needs to distrust. 0 disables flagging.
    cost_residual_warn_ratio: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_COST_RESIDUAL_WARN_RATIO", 4.0,
            "cost_residual_warn_ratio", minimum=0.0,
        )
    )
    # Always-on cost/memory ledger (`runtime.costmodel`): every XLA
    # shape specialization of a cached program captures the compiler's
    # modeled flops / HBM bytes (from the lowered module's cost
    # analysis — no second XLA compile) plus exact argument/output
    # byte counts, and every dispatch counts against its shape entry,
    # so `tfs.diagnostics()` can report achieved-vs-peak fractions per
    # program fingerprint without ever re-lowering a graph. Capture
    # cost is paid only at compile events; steady-state dispatches pay
    # one dict update. Independent of `telemetry` (the ledger is on
    # even when span recording is off). Env override TFS_COST_LEDGER
    # ("0" disables) seeds the initial value.
    cost_ledger: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_COST_LEDGER", True, "cost_ledger"
        )
    )
    # Deep memory capture: additionally compile the lowered module at
    # capture time to read `memory_analysis()` (temp/scratch bytes —
    # the part of the footprint avals cannot model). DOUBLES the XLA
    # compile cost of every new program shape, so it is opt-in; with it
    # off the modeled footprint is argument + output bytes and
    # `temp_bytes` reads honest None.
    cost_ledger_memory: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_COST_LEDGER_MEMORY", False, "cost_ledger_memory"
        )
    )
    # Fault-tolerant dispatch (`runtime.faults`): every block execution
    # is a pure function of (compiled executable, block arrays) — the
    # property the reference leaned on for Spark task retry — so a
    # failed dispatch can be re-run. Errors are CLASSIFIED: only
    # ``transient`` failures (device lost/preempted, UNAVAILABLE /
    # INTERNAL / DATA_LOSS runtime statuses) consume retry attempts;
    # ``deterministic`` errors (dtype/shape bugs, check_numerics
    # FloatingPointError) surface after exactly one attempt, and
    # ``resource`` errors (RESOURCE_EXHAUSTED / OOM) trigger block
    # splitting instead (see oom_split_depth).
    #
    # block_retry_attempts: extra attempts per block dispatch for
    # transient errors (changed semantics vs the pre-classification
    # blanket retry, which burned attempts on deterministic errors too).
    block_retry_attempts: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_BLOCK_RETRY_ATTEMPTS", 3, "block_retry_attempts",
            minimum=0,
        )
    )
    # verb_retry_budget: total transient retries ONE verb call may spend
    # across all its block dispatches — bounds the worst-case stall of a
    # verb over many blocks on a flapping device.
    verb_retry_budget: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_VERB_RETRY_BUDGET", 32, "verb_retry_budget", minimum=0
        )
    )
    # Exponential backoff between transient retries: base * 2^(k-1)
    # capped at max, times a DETERMINISTIC jitter factor in
    # [1, 1+retry_jitter] seeded by (retry_seed, dispatch, attempt) —
    # reruns sleep the same schedule, so fault-injected tests reproduce.
    retry_backoff_base_s: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_RETRY_BACKOFF_BASE_S", 0.05, "retry_backoff_base_s",
            # a negative backoff would feed time.sleep() a ValueError
            # mid-retry — clamp, mirroring the int helpers' minimum=
            minimum=0.0,
        )
    )
    retry_backoff_max_s: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_RETRY_BACKOFF_MAX_S", 2.0, "retry_backoff_max_s",
            minimum=0.0,
        )
    )
    retry_jitter: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_RETRY_JITTER", 0.25, "retry_jitter", minimum=0.0
        )
    )
    retry_seed: int = dataclasses.field(
        default_factory=lambda: _env_int("TFS_RETRY_SEED", 0, "retry_seed")
    )
    # OOM graceful degradation: a resource-classified block dispatch
    # splits the block in half (down the shape-bucketing ladder) and
    # re-dispatches, up to this many recursive halvings. Row-local maps
    # concatenate the halves; monoid-classified reduces combine them
    # (size-weighted for mean); unclassifiable graphs re-raise the
    # original error exactly. 0 disables splitting.
    oom_split_depth: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_OOM_SPLIT_DEPTH", 3, "oom_split_depth", minimum=0
        )
    )
    # Device failover (`runtime.scheduler.DeviceHealth`): a transient
    # dispatch failure opens the device's circuit for this many seconds
    # (doubling on repeated failures, capped at 8x); its unissued blocks
    # re-place LPT onto healthy devices, and after the cooldown ONE
    # half-open probe dispatch re-admits it on success. Explicit
    # ``devices=`` pins opt out of failover (with a loud warning when a
    # pinned device is circuit-open).
    device_cooldown_s: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_DEVICE_COOLDOWN_S", 30.0, "device_cooldown_s",
            minimum=0.0,
        )
    )
    # Deadline / cancellation (`runtime.deadline`): default time budget
    # for a TOP-LEVEL verb call when no per-call timeout_s= is given
    # (0 = unbounded, the library default). The budget is an ABSOLUTE
    # deadline propagated through a contextvar, so everything a verb
    # starts (lazy force, stream chunks, combines, backoff sleeps,
    # ingest stages) shares one clock; expiry raises DeadlineExceeded
    # (classified deterministic — never burned as a retry). Env
    # override TFS_DEFAULT_VERB_TIMEOUT_S seeds the initial value.
    default_verb_timeout_s: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_DEFAULT_VERB_TIMEOUT_S", 0.0, "default_verb_timeout_s"
        )
    )
    # Admission control (`runtime.deadline.AdmissionController`): max
    # TOP-LEVEL verbs in flight at once (0 = unlimited). Nested verbs
    # (a stream's per-chunk reduce, a lazy terminal's force) never take
    # a second slot, so small limits cannot deadlock. Env override
    # TFS_MAX_CONCURRENT_VERBS seeds the initial value — the serving
    # lane's knob.
    max_concurrent_verbs: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_MAX_CONCURRENT_VERBS", 0, "max_concurrent_verbs"
        )
    )
    # Bounded admission wait queue: callers beyond the concurrency
    # limit queue up to this many deep; arrivals at a full queue are
    # SHED immediately with a typed OverloadError (queue depth +
    # retry-after hint from the live verb_seconds histogram). 0 = shed
    # the moment the limit is reached (no queueing). Env override
    # TFS_ADMISSION_QUEUE_LIMIT seeds the initial value — the sibling
    # of TFS_MAX_CONCURRENT_VERBS, so both admission knobs deploy
    # without code changes.
    admission_queue_limit: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_ADMISSION_QUEUE_LIMIT", 32, "admission_queue_limit"
        )
    )
    # Max seconds a queued caller waits for a slot before being shed
    # (its own deadline still applies and may fire first). 0 = wait
    # bounded only by the caller's deadline — do not combine 0 with
    # un-deadlined callers in a service, or a stuck verb strands its
    # whole queue.
    admission_wait_timeout_s: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_ADMISSION_WAIT_TIMEOUT_S", 30.0,
            "admission_wait_timeout_s", minimum=0.0,
        )
    )
    # Serving runtime (`serving/`): the multi-tenant front-end that
    # keeps registered endpoint programs warm and coalesces concurrent
    # small requests into one bucketed dispatch.
    #
    # serve_batch_window_ms: how long the micro-batcher holds an open
    # batch for more requests before dispatching. A batch also closes
    # EARLY the moment its row total lands exactly on a bucket-ladder
    # rung (padding waste zero — waiting longer could only push it to
    # the next rung) or reaches serve_max_batch_rows. 0 disables
    # coalescing entirely: every request dispatches alone. Env override
    # TFS_SERVE_BATCH_WINDOW_MS seeds the initial value.
    serve_batch_window_ms: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_SERVE_BATCH_WINDOW_MS", 5.0, "serve_batch_window_ms"
        )
    )
    # serve_max_batch_rows: ceiling on one coalesced dispatch AND the
    # top of the bucket ladder `serving.register(warm=True)` compiles
    # at registration — requests whose batches stay under it hit only
    # warmed rungs (zero steady-state compiles, asserted by
    # tests/test_serving.py). A single oversized request still dispatches
    # (alone), paying its own compile. Env override
    # TFS_SERVE_MAX_BATCH_ROWS seeds the initial value.
    serve_max_batch_rows: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_SERVE_MAX_BATCH_ROWS", 4096, "serve_max_batch_rows"
        )
    )
    # serve_queue_limit: max requests queued per (endpoint x program)
    # batching lane; arrivals beyond it are SHED immediately with a
    # typed OverloadError (HTTP 429 + Retry-After at the server) so a
    # slow endpoint builds bounded queues, never unbounded latency.
    # 0 = unlimited (bounded only by admission control + deadlines).
    # Env override TFS_SERVE_QUEUE_LIMIT seeds the initial value so a
    # tuned deployment needs no code change.
    serve_queue_limit: int = dataclasses.field(
        default_factory=lambda: _env_int(
            "TFS_SERVE_QUEUE_LIMIT", 256, "serve_queue_limit"
        )
    )
    # serve_default_timeout_s: per-request deadline the server applies
    # when the client sends no X-TFS-Timeout-S header. Unlike
    # default_verb_timeout_s (a library-wide opt-in), a serving request
    # ALWAYS has a budget — an un-deadlined request behind a wedged
    # endpoint would strand its server thread forever. Env override
    # TFS_SERVE_DEFAULT_TIMEOUT_S seeds the initial value.
    serve_default_timeout_s: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_SERVE_DEFAULT_TIMEOUT_S", 30.0, "serve_default_timeout_s"
        )
    )
    # serve_warm_compile: compile every bucket-ladder rung up to
    # serve_max_batch_rows at `serving.register()` time (row-local
    # endpoints only — others cannot pad, so rung warming cannot cover
    # their request sizes). Off = first requests pay the compiles.
    serve_warm_compile: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_SERVE_WARM_COMPILE", True, "serve_warm_compile"
        )
    )
    # Device-grant watchdog (`runtime.faults.device_grant`): when > 0,
    # the scheduler's device acquisition runs under a watchdog thread
    # and raises `faults.DeviceGrantTimeout` if the accelerator backend
    # wedges at device grant for this long. 0 disables the watchdog.
    # Env override TFS_DEVICE_GRANT_TIMEOUT_S seeds the initial value.
    device_grant_timeout_s: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_DEVICE_GRANT_TIMEOUT_S", 0.0, "device_grant_timeout_s"
        )
    )
    # Closed-loop autotuner (`runtime.autotune`): when on, a background
    # daemon thread periodically snapshots the live workload profile
    # and nudges the UNPINNED performance knobs (bucket-ladder
    # growth/min, ingest decode workers / prefetch depth, per-endpoint
    # serving batch window, max_concurrent_verbs) toward what the
    # telemetry says the workload wants — hysteresis dead-bands + step
    # and safety bounds keep it from oscillating, and a knob set
    # explicitly (update()/override()/TFS_* env) is NEVER touched. Off
    # (the default) = zero behavior change: no thread starts and no
    # knob is ever mutated; `tfs.autotune()` stays available for
    # one-shot offline tuning either way. Env override TFS_AUTOTUNE
    # seeds the initial value.
    autotune: bool = dataclasses.field(
        default_factory=lambda: _env_bool("TFS_AUTOTUNE", False, "autotune")
    )
    # Seconds between background tuning cycles (each cycle: snapshot ->
    # recommend -> apply). Env override TFS_AUTOTUNE_INTERVAL_S.
    autotune_interval_s: float = dataclasses.field(
        default_factory=lambda: _env_float(
            "TFS_AUTOTUNE_INTERVAL_S", 30.0, "autotune_interval_s"
        )
    )
    # Debug mode: raise on NaN/Inf in any verb output (block + fetch named).
    check_numerics: bool = dataclasses.field(
        default_factory=lambda: _env_bool(
            "TFS_CHECK_NUMERICS", False, "check_numerics"
        )
    )
    # Route verbs through the C++ PJRT host (`runtime.native_executor`)
    # when no explicit executor= is passed — the SURVEY §2.4 framing:
    # the native host is the libtensorflow-equivalent spine, not an
    # opt-in. Values:
    #   "off"  — in-process JAX executor (jaxlib is itself a native
    #            runtime; this remains the safe default)
    #   "auto" — use NativeExecutor over the repo-built CPU plugin when
    #            it is present; silently fall back to in-process JAX
    #            when it is not. Mesh kinds on the single-device plugin
    #            fall back to in-process JAX per the documented
    #            NativeExecutor(jax_fallback=True) semantics (safe: the
    #            repo CPU plugin claims no shared accelerator device).
    #   "require" — like "auto" but raise if the plugin is unavailable
    #            (the CI native lane uses this so silent fallback can
    #            never mask a broken build).
    # Env override TFS_NATIVE_EXECUTOR seeds the initial value so a CI
    # lane can run the whole verb suite under the native default.
    native_executor: str = dataclasses.field(
        default_factory=lambda: _env_str(
            "TFS_NATIVE_EXECUTOR", "off", "native_executor"
        )
    )

    def lax_precision(self):
        from jax import lax

        return {
            "highest": lax.Precision.HIGHEST,
            "tensorfloat32": lax.Precision.HIGH,
            "default": lax.Precision.DEFAULT,
        }[self.matmul_precision]


_config = Config()

# ---------------------------------------------------------------------------
# pin / tuned-value bookkeeping (see the module docstring)
# ---------------------------------------------------------------------------

# one lock serializes every pin/tuned mutation (update / set_tuned /
# reset_tuning): the autotuner runs on a background thread, and the
# "pins win, always" contract needs check-then-write to be atomic —
# an operator update() racing a set_tuned() must never lose
import threading as _threading

_state_lock = _threading.Lock()

# knobs the OPERATOR set: update()/override() calls plus well-formed
# TFS_* env seeds captured while _config was constructed above. The
# autotuner must never write these.
_EXPLICIT: set = set(_ENV_SEEDED)
# knobs the AUTOTUNER currently owns -> the value it applied. Distinct
# from _EXPLICIT so diagnostics can say which values are tuned, and so
# reset_tuning() knows what to restore.
_TUNED: dict = {}

_MISSING = object()


def explicit_keys() -> frozenset:
    """Knobs pinned by the operator (update()/override()/env) — the
    set the autotuner's "never fight a pin" rule checks against."""
    return frozenset(_EXPLICIT)


def is_explicit(key: str) -> bool:
    return key in _EXPLICIT


def tuned() -> dict:
    """``{knob: value}`` currently owned by the autotuner."""
    return dict(_TUNED)


def default_value(key: str):
    """The knob's baseline: the dataclass default, env-seeded the same
    way the process's initial config was — what `reset_tuning` restores
    and what policies treat as "the static default"."""
    base = Config()
    if not hasattr(base, key):
        raise AttributeError(f"unknown config key {key!r}")
    return getattr(base, key)


def set_tuned(key: str, value) -> bool:
    """The autotuner's ONLY write path: apply ``value`` unless the knob
    is explicitly pinned. Returns False (and changes nothing) for a
    pinned knob — an operator's explicit setting always wins. The
    pin check and the write are one atomic step under the state lock,
    so a concurrent `update()` can never be overwritten."""
    if not hasattr(_config, key):
        raise AttributeError(f"unknown config key {key!r}")
    with _state_lock:
        if key in _EXPLICIT:
            return False
        setattr(_config, key, value)
        _TUNED[key] = value
    return True


def reset_tuning() -> None:
    """Restore every tuned knob to its (env-seeded) default and forget
    the tuned set — the test-isolation hook, and the operator's undo."""
    if not _TUNED:
        return
    base = Config()
    with _state_lock:
        for k in list(_TUNED):
            setattr(_config, k, getattr(base, k))
        _TUNED.clear()


def get() -> Config:
    return _config


def update(**kwargs) -> None:
    for k, v in kwargs.items():
        if not hasattr(_config, k):
            raise AttributeError(f"unknown config key {k!r}")
        with _state_lock:
            setattr(_config, k, v)
            # an explicit set PINS the knob: the autotuner may no
            # longer touch it, and any tuned value it carried is
            # superseded
            _EXPLICIT.add(k)
            _TUNED.pop(k, None)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. A directory given from outside wins: where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it into
    ``jax_compilation_cache_dir`` and this sets no other. Otherwise the
    cache is ``<checkout>/.jax_cache``, resolved from this package's own
    location — a fixed path, because the path is part of the cache key
    (a temp name, pid or time would never hit). Entry points
    (`chip_smoke.py`, `perf/run.py`) call this at start-up; importing
    the package never does."""
    import os

    import jax

    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        )
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only those that took over a second to
    # compile: a verb's many small block programs are most of a cold run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@contextlib.contextmanager
def override(**kwargs):
    old = {k: getattr(_config, k) for k in kwargs}
    # pin state is scoped like the values: a knob pinned only inside an
    # override() is un-pinned again on exit (and a tuned value it
    # shadowed is restored to the tuned ledger)
    old_explicit = {k: (k in _EXPLICIT) for k in kwargs}
    old_tuned = {k: _TUNED.get(k, _MISSING) for k in kwargs}
    update(**kwargs)
    try:
        yield _config
    finally:
        update(**old)
        # the pin/ledger restore shares the state lock with
        # set_tuned(): a background tuner write interleaving here
        # would otherwise desync _TUNED from the value in force
        with _state_lock:
            for k in kwargs:
                if not old_explicit[k]:
                    _EXPLICIT.discard(k)
                if old_tuned[k] is not _MISSING:
                    _TUNED[k] = old_tuned[k]
