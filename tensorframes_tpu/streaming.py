"""Out-of-core streaming reduce (extracted from `api.py`).

`reduce_blocks_stream` folds an iterator of frames with background
prefetch and bounded-memory tree-folding — the Spark-spill analogue
that makes the BASELINE north star (1B-row vector reduce) run in
bounded host memory. `api.py` re-exports both names, so the public
surface is unchanged.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import config as _config
from .aggregate import _chunk_combiners
from .frame import TensorFrame
from .graph.analysis import analyze_graph
from .graph.ir import base_name as _base
from .runtime.executor import Executor
from .utils import telemetry as _telemetry
from .utils.profiling import count as record_count, record

# late-bound: api imports this module, so helper lookups resolve at
# call time through the module object (same pattern as parallel/verbs)
from . import api as _api

from .api import Fetches  # noqa: E402,F401  (annotations; api is mid-init
# but Fetches is defined before this module loads)


def _prefetch_iter(it, depth=None, stage=None):
    """Pull ``it`` on a daemon thread, ``depth`` items ahead (default
    ``config.stream_prefetch_depth``). The consumer (device execution)
    and the producer (chunk synthesis / host IO) then overlap — the
    streaming analogue of Spark's pipelined partition fetch.

    ``stage`` (optional) is a per-item transform run on ANOTHER
    pipeline thread between producer and consumer — the device-transfer
    stage: when it issues `jax.device_put` for chunk k+1, that H2D copy
    proceeds under chunk k's compute, double-buffering transfer against
    execution end to end. A stage failure propagates to the consumer
    like a producer failure (stamped with chunk index + stage name).

    Since ISSUE 7 this is a thin wrapper over the generic stage-graph
    runtime (`ingest.pipeline.pipelined`), which owns the shared
    buffering budget, per-stage telemetry, classified fault retries and
    cancellation; `reduce_blocks_stream` composes richer graphs
    (parallel decode of multi-file datasets) through the same runtime.
    """
    from .ingest.pipeline import PipeStage, pipelined

    stages = [] if stage is None else [PipeStage("transfer-stage", stage)]
    return pipelined(it, stages, depth=depth)


def _spill_partial_to_host(part: Dict, chunk: int) -> Dict:
    """D2H-spill one partial table to host numpy through the ONE
    accounting path every stream spill shares: a ``host_sync`` span +
    counter and the ``d2h_bytes`` histogram. The unfoldable-stream
    spill, the double-buffer stand-down and the materialization cache's
    serialize step all report through this shape, so diagnostics see
    every forced device-to-host sync the same way. Host-resident
    partials pass through untouched (and cost nothing)."""
    if all(isinstance(v, np.ndarray) for v in part.values()):
        return part
    with _telemetry.span(
        "reduce_blocks_stream.spill", kind="host_sync", chunk=chunk,
    ):
        spilled = {k: np.asarray(v) for k, v in part.items()}
    record_count("host_sync")
    if _telemetry.enabled():
        _telemetry.histogram_observe(
            "d2h_bytes",
            float(sum(v.nbytes for v in spilled.values())),
        )
    return spilled


from .runtime.deadline import deadline_entry as _deadline_entry


@_deadline_entry("reduce_blocks_stream")
def reduce_blocks_stream(
    fetches: Fetches,
    frames,
    feed_dict: Optional[Dict[str, str]] = None,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
    mesh=None,
    fold_every="auto",
    devices=None,
    checkpoint=None,
    checkpoint_every: Optional[int] = None,
    resume: str = "auto",
):
    """Out-of-core reduce: fold an ITERATOR of frames (chunks too large to
    hold at once — the Spark-spill analogue). Chunk N+1 is produced by a
    background prefetch thread while chunk N reduces on device, so host
    synthesis/IO overlaps device execution; partials combine with the
    same graph.

    The partial table itself is tree-folded every ``fold_every`` chunks,
    so host memory is bounded by O(fold_every) partials no matter how
    long the stream — the streaming form is what makes the BASELINE
    north star (1B-row vector reduce_sum) run in bounded host memory
    unconditionally.

    Chunks may be `LazyFrame`s (a pending map chain over each chunk):
    the per-chunk dispatch routes through the lazy terminal, so each
    chunk's map stages and its block reduce run as ONE fused program
    per block — the combine over partials still runs the plain reduce
    graph, so fold semantics are unchanged.

    Combining partials through the same graph assumes the reduce is
    ASSOCIATIVE over blocks (sum/min/max/...) — the same contract as the
    reference's pairwise partial combine (`reducePairBlock`,
    `DebugRowOps.scala:748-757`). A non-associative graph (e.g. Mean:
    a fold result re-enters the next combine weighted as ONE chunk) is
    not exact under tree-folding, so the default ``fold_every="auto"``
    enables tree-folding (every 64 chunks) ONLY when every fetch is an
    associative monoid reduce (sum/min/max/prod) consuming its
    placeholder DIRECTLY — partials recombine through the same graph,
    so any transform between placeholder and reduce (``Sum(x*x)``)
    would be re-applied to the partials at each fold. Mean,
    transform-then-reduce, and unclassifiable graphs fall back to the
    single equally-weighted final combine at the cost of O(#chunks)
    host memory. Pass an int to force a fold cadence, or ``None`` to
    force the single final combine.

    Durable streams (``checkpoint=``, `runtime.checkpoint`): give a
    path and the stream atomically commits its progress — a versioned
    manifest (dataset/program/config fingerprints, per-fetch monoid
    kinds, the contiguous-chunk WATERMARK) plus the live partial table
    — after every ``checkpoint_every`` folded chunks (default
    ``config.stream_checkpoint_every``), on clean `DeadlineExceeded` /
    `Cancelled` exits, and at completion. A crash / SIGKILL /
    preemption then resumes in a fresh process: the committed manifest
    is validated field by field (any drift refuses loudly naming the
    field; ``resume="ignore"`` opts into a fresh start), chunks below
    the watermark are skipped at the `Dataset.tasks()` METADATA level
    (never re-decoded) for an unstarted `IngestStream`, and the fold
    is seeded with the restored partials — bit-identical to an
    uninterrupted run for exact monoids (min/max/prod/int-sum), within
    the documented reassociation tolerance for float sum/mean. Only
    classifiable monoid reduces are eligible; anything else rejects
    ``checkpoint=`` with a typed `CheckpointError`. Requires the local
    path (no ``mesh=``).
    """
    graph, fetch_list = _api._as_graph(fetches, fetch_names)
    auto_fold = fold_every == "auto"
    if auto_fold:
        fold_every = None  # resolved from the first chunk's analysis below
    if fold_every is not None:
        fold_every = max(2, int(fold_every))

    def _combine(parts: List[Dict]) -> Dict:
        # device partials stack on device (one dispatch, no host
        # round-trip between fold generations); host partials stay host.
        # Rotated-device partials converge on the schedule's anchor so
        # the stacked frame's columns share one committed device.
        anchor = sched_devs[0] if sched_devs else None
        stacked = TensorFrame.from_dict(
            {
                b: _api._stack_parts([p[b] for p in parts], anchor)
                for b in parts[0]
            }
        )
        with contextlib.ExitStack() as _gstack:
            if gmesh_off[0]:
                # the stream already proved this graph unclassifiable:
                # the combine must not re-probe (and re-count) it
                from . import globalframe as _gfm

                _gstack.enter_context(_gfm._suppress_route())
            r = _api.reduce_blocks(
                graph, stacked, None, fetch_names=fetch_list,
                executor=executor,
                # the combine honors the stream's device set (a pinned
                # stream keeps its combine on the pinned device; rotation
                # anchors it on sched_devs[0] where the stack landed)
                devices=list(sched_devs) if sched_devs else None,
            )
        return r if isinstance(r, dict) else {_base(fetch_list[0]): r}

    transfer_warned = [False]
    # global-mode sharded transfer stands down for the REST of the
    # stream once conversion fails or the consume loop's first-chunk
    # eligibility gate finds the reduce graph unclassifiable (the
    # transfer stage runs on a pipeline thread — the plain-list cell
    # is the shared off switch, and a lagging read merely shards one
    # extra chunk, which the consume loop converts back)
    gmesh_off = [False]
    gmesh_checked = [False]
    # Block-scheduled streams round-robin chunks over the device set:
    # the prefetch transfer stage targets the NEXT chunk's assigned
    # device, so each device's H2D copy double-buffers under the
    # previous chunk's compute on a DIFFERENT device, and the per-chunk
    # reduce below pins its dispatch to the same device. Both sides
    # derive the assignment from the same chunk ordinal (the stage
    # processes items in stream order on one thread), so they can never
    # disagree.
    stage_idx = [0]
    consume_idx = [0]

    def _chunk_device(counter):
        # the ordinal advances for EVERY chunk, even while rotation is
        # off (sched_devs None): if the global path stands down
        # mid-stream and rotation resumes, both the transfer stage and
        # the consume loop must map chunk i to the same device, so the
        # assignment has to key off the chunk ordinal, not off how many
        # chunks each side happened to rotate
        i = counter[0]
        counter[0] += 1
        if not sched_devs:
            return None
        return sched_devs[i % len(sched_devs)]

    def _to_device(f):
        # the transfer stage of the prefetch pipeline: issue the H2D
        # copy of chunk k+1 while chunk k computes. Only for the
        # local single-device path — the mesh path owns its own
        # sharded placement — and only for real frames (tests feed
        # plain dicts through here). Already-device columns pass
        # through untouched (to_device skips them; with a scheduled
        # target device they commit/move there). LazyFrame chunks
        # stage their BASE frame (the pending plan rides along and
        # fuses with the reduce at dispatch below).
        dev = _chunk_device(stage_idx)  # every item advances the ordinal
        from .lazy import LazyFrame

        if gmesh is not None and isinstance(f, TensorFrame):
            # global stream path: the transfer stage does the SHARDED
            # device_put (per-shard H2D copies overlap under the
            # previous chunk's compute), and the per-chunk reduce below
            # folds into the sharded accumulator as ONE SPMD dispatch.
            # Small/ineligible chunks stay plain and fall THROUGH to
            # the ordinary per-block transfer below — they keep the
            # H2D/compute overlap, the same fallback rule as the verbs.
            from . import config as _cfg
            from . import globalframe as _gf

            if (
                not gmesh_off[0]
                and f.nrows >= max(1, _cfg.get().global_frame_min_rows)
            ):
                try:
                    return _gf.GlobalFrame.from_frame(f, mesh=gmesh)
                except Exception as e:
                    gmesh_off[0] = True
                    from .utils.log import get_logger

                    get_logger("streaming").warning(
                        "global sharded transfer disabled for this "
                        "stream (%s: %s); chunks fall back to the "
                        "per-block path",
                        type(e).__name__, e,
                    )
        if isinstance(f, (LazyFrame, TensorFrame)):
            try:
                return f.to_device(device=dev)
            except Exception as e:
                # fall back to host arrays (the reduce dispatch will
                # transfer implicitly) — but say so ONCE: a silently
                # degraded stream would report serial transfer as an
                # overlap regression with no clue why
                if not transfer_warned[0]:
                    transfer_warned[0] = True
                    from .utils.log import get_logger

                    get_logger("streaming").warning(
                        "prefetch device-transfer stage disabled for "
                        "this stream (%s: %s); chunks will transfer "
                        "synchronously inside each reduce dispatch",
                        type(e).__name__, e,
                    )
                return f
        return f

    from .runtime.executor import default_executor
    from .runtime import scheduler as _rs

    # No transfer stage for the mesh path (it owns its sharded
    # placement) or a native-host executor (`.host`): device_put would
    # initialize the in-process JAX backend next to a host that may own
    # the same device.
    ex = executor if executor is not None else default_executor()
    local = mesh is None and getattr(ex, "host", None) is None
    if devices is not None and not local:
        raise ValueError(
            "reduce_blocks_stream: devices= requires the local in-process "
            "path (no mesh=, no native-host executor)"
        )
    sched_devs = (
        _rs.resolve(devices=devices, executor=ex) if local else None
    )
    if sched_devs is not None and devices is None and len(sched_devs) < 2:
        # auto-resolved to one device: plain prefetch, nothing to
        # rotate. An EXPLICIT one-device list stays: rotation over one
        # device IS the documented pin (every chunk targets it).
        sched_devs = None
    # block_scheduler="global": eligible chunks shard over ONE data
    # mesh in the transfer stage and each chunk's reduce is a single
    # SPMD dispatch — the global path owns placement, so the per-chunk
    # device rotation stands down (an explicit devices= pin keeps it).
    gmesh = None
    gmesh_rotation = None
    if local and devices is None and _rs.global_mode():
        from . import globalframe as _gf

        try:
            gmesh = _gf.resolve_global_mesh()
        except Exception:
            gmesh = None
        if gmesh is not None:
            # parked, not dropped: if the stream stands the global path
            # down (unclassifiable reduce, failed conversion), per-chunk
            # rotation resumes — ineligible streams behave as "auto"
            gmesh_rotation, sched_devs = sched_devs, None
    # Compose ONE stage graph for the whole ingest path. A plain
    # iterator of frames keeps the classic producer -> transfer shape;
    # an `IngestStream` (multi-file dataset from `stream_dataset` /
    # multi-path io readers) contributes its discovery source and
    # parallel-decode stage, so discovery, decode, H2D transfer,
    # compute and combine all overlap under one shared buffering
    # budget instead of two chained pipelines.
    from .ingest.dataset import IngestStream
    from .ingest.pipeline import PipeStage, pipelined

    composable = isinstance(frames, IngestStream) and not frames.started

    ckpt = None
    watermark = 0
    restored: List[Dict] = []
    ds_tasks = None
    if checkpoint is not None:
        from .runtime.checkpoint import CheckpointError, StreamCheckpointer

        if mesh is not None:
            raise CheckpointError(
                "checkpoint= requires the local path (no mesh= — the "
                "mesh owns its own placement and has no per-chunk "
                "watermark to commit)"
            )
        ds_fp = None
        if composable:
            # the dataset fingerprint AND the resume skip both work at
            # the task-METADATA level: materializing the task list here
            # reads only file footers, never chunk data
            ds_tasks = frames.dataset.task_list()
            ds_fp = frames.dataset.fingerprint(ds_tasks)
        ckpt = StreamCheckpointer(
            checkpoint, graph, [_base(f) for f in fetch_list],
            checkpoint_every, resume, ds_fp,
        )
        ckpt.entry_gate()
        watermark, restored = ckpt.try_resume()

    if composable:
        # resume skips committed chunks at the task level: they are
        # never decoded again (the decode-stage counter proves it)
        source, pipe_stages = frames.source_and_stages(
            tasks=ds_tasks, skip=watermark
        )
        pipe_depth = frames.depth
    else:
        # plain iterator — or an IngestStream someone already pulled
        # from, whose running pipeline must be consumed, not rebuilt
        source, pipe_stages, pipe_depth = frames, [], None
        if watermark:
            # a plain iterator has no metadata level: committed chunks
            # are pulled (the producer pays their synthesis) but never
            # transferred or dispatched
            source = iter(frames)
            for _ in range(watermark):
                try:
                    next(source)
                except StopIteration:
                    break
    if watermark:
        # device rotation continues from the committed ordinal, as if
        # the stream had never stopped
        stage_idx[0] = consume_idx[0] = watermark
    if local:
        pipe_stages.append(PipeStage("transfer-stage", _to_device))

    from .runtime.deadline import Cancelled, DeadlineExceeded

    partials: List[Dict] = list(restored)
    # Double-buffered accumulator (global streaming path): instead of
    # parking ``fold_every`` partials and tree-folding them in one
    # burst, fold chunk k's partial eagerly into one of TWO alternating
    # slots. Each slot is an independent dependency chain, so the async
    # one-SPMD-dispatch fold of chunk k (slot k%2) runs while chunk
    # k+1's sharded device_put is in flight AND chunk k+1's own fold
    # lands on the OTHER slot — the fold never serializes against the
    # H2D transfer. Device-resident partials drop from O(fold_every)
    # parked tables to O(2). Active only for streams that would
    # tree-fold anyway (same associativity contract; pairwise
    # reassociation stays within the documented float-sum tolerance;
    # min/max/prod/int-sum are exact), never for durable streams (the
    # checkpoint protocol commits the partials LIST), and gated on
    # ``config.plan_pipeline`` so a test can hold it still.
    dbuf: List[Optional[Dict]] = [None, None]
    dbuf_n = [0]
    dbuf_ok = [True]
    # `ordinal` counts source chunks FULLY consumed (committed ones
    # included): the candidate watermark. Empty chunks advance it —
    # they contribute the reduction identity, and a resume must not
    # re-deliver them just to skip them again.
    ordinal = watermark
    try:
        for f in pipelined(
            source, pipe_stages, depth=pipe_depth, ordinal_base=watermark
        ):
            if gmesh_off[0] and gmesh_rotation is not None:
                # the global path stood down: rotation resumes exactly
                # as under "auto" (chunks transferred before the switch
                # pay at most one implicit move onto their pinned
                # device — bounded by the prefetch depth)
                sched_devs = gmesh_rotation
                gmesh_rotation = None
            chunk_dev = _chunk_device(consume_idx)
            nrows = len(f) if _api._is_pandas(f) else getattr(f, "nrows", None)
            if nrows == 0:
                # Empty chunk (empty file partition / fully filtered
                # shard): it contributes the reduction identity, i.e.
                # nothing — skip the dispatch instead of raising "empty
                # frame" mid-stream or emitting a partial that poisons
                # the combine (reduce_min over 0 rows). Classification
                # (auto_fold) waits for the first chunk that actually
                # carries rows.
                ordinal += 1
                continue
            if gmesh is not None:
                from . import globalframe as _gfm

                if isinstance(f, _gfm.GlobalFrame) and not gmesh_off[0]:
                    if not gmesh_checked[0]:
                        # the reduce graph is fixed for the stream's
                        # lifetime: decide ONCE whether it lowers to
                        # the one-dispatch collective program, instead
                        # of paying a sharded H2D plus a local-boundary
                        # fallback re-gather on every chunk
                        gmesh_checked[0] = True
                        if not _gfm.stream_reduce_eligible(
                            graph, fetch_list, f, feed_dict, executor
                        ):
                            gmesh_off[0] = True
                            # ONE counted reason for the whole stream,
                            # not one per chunk
                            _gfm._note_fallback("unclassified-reduce")
                            from .utils.log import get_logger

                            get_logger("streaming").warning(
                                "global sharded transfer disabled for "
                                "this stream: the reduce graph has no "
                                "monoid structure to lower as an "
                                "in-program collective; chunks take "
                                "the per-block path"
                            )
                if gmesh_off[0] and isinstance(f, _gfm.GlobalFrame):
                    # sharded before the off switch flipped (in-flight
                    # prefetch, or the gate's own first chunk)
                    f = f.to_frame()
            if auto_fold or (ckpt is not None and ckpt.monoids is None):
                # classify once, on the first chunk: ONE analysis pass
                # serves both the fold class (tree-fold only graphs
                # proven associative — sum/min/max/prod monoids
                # consuming their placeholder directly; anything else
                # keeps every partial for one exact final combine) and
                # the checkpoint eligibility gate / monoid manifest
                comb_any = None
                try:
                    ov = _api._ph_overrides(
                        graph, f, feed_dict, block_level=True
                    )
                    s = analyze_graph(
                        graph, fetch_list, placeholder_shapes=ov
                    )
                    comb_any = _chunk_combiners(graph, fetch_list, s)
                    if auto_fold:
                        # require_direct: partials recombine through
                        # the same graph here, so an interposed
                        # transform (Sum(x*x)) would be re-applied at
                        # every fold
                        comb = _chunk_combiners(
                            graph, fetch_list, s, require_direct=True
                        )
                        if comb is not None and "mean" not in comb.values():
                            fold_every = 64
                except Exception:
                    pass  # conservative: no folding when classification fails
                auto_fold = False
                if ckpt is not None:
                    # rejects non-classifiable reduces (typed
                    # CheckpointError) and, on resume, refuses a
                    # drifted monoid set / fold cadence
                    ckpt.on_first_chunk(comb_any, fold_every)
            # per-chunk span/counters: stream chunks previously bypassed
            # profiling entirely (only the inner verb recorded); the chunk
            # record attributes each dispatch to the stream and carries the
            # chunk row count
            with record("reduce_blocks_stream.chunk", int(nrows or 0)), \
                    contextlib.ExitStack() as _gstack:
                if gmesh_off[0]:
                    # the stream already decided against the global
                    # path: stop the per-chunk auto-route from
                    # re-probing (and re-counting a fallback for) the
                    # same fixed graph on every chunk
                    from . import globalframe as _gfm

                    _gstack.enter_context(_gfm._suppress_route())
                r = _api.reduce_blocks(
                    graph, f, feed_dict, fetch_names=fetch_list,
                    executor=executor, mesh=mesh,
                    # pin the chunk's dispatch to the device its prefetch
                    # transfer targeted: compute lands where the data
                    # already is, and consecutive chunks run on different
                    # devices (compute/compute overlap, not just
                    # transfer/compute)
                    devices=[chunk_dev] if chunk_dev is not None else None,
                )
            part = r if isinstance(r, dict) else {_base(fetch_list[0]): r}
            use_dbuf = (
                dbuf_ok[0] and gmesh is not None and not gmesh_off[0]
                and ckpt is None and fold_every is not None
                and _config.get().plan_pipeline
            )
            if use_dbuf:
                slot = dbuf_n[0] % 2
                dbuf_n[0] += 1
                if dbuf[slot] is None:
                    dbuf[slot] = part
                else:
                    try:
                        with _telemetry.span(
                            "reduce_blocks_stream.fold", kind="stage",
                            slot=slot,
                        ):
                            dbuf[slot] = _combine([dbuf[slot], part])
                        from . import globalframe as _gfm

                        _gfm._note_stream_fold()
                    except Exception:
                        # device pressure (or anything else) mid-fold:
                        # spill both operands to host through the
                        # shared D2H accounting path and stand down to
                        # the tree-fold list for the rest of the stream
                        dbuf_ok[0] = False
                        partials.extend(
                            _spill_partial_to_host(p, ordinal)
                            for p in (dbuf[slot], part)
                        )
                        dbuf[slot] = None
                ordinal += 1
            else:
                partials.append(part)
                # advance the candidate watermark the moment the
                # chunk's contribution is IN `partials`: from here on
                # (ordinal, partials) is a committable state even if
                # the fold below is interrupted mid-combine (a fold
                # only reorganizes contributions, it never adds one)
                ordinal += 1
                if fold_every is not None and len(partials) >= fold_every:
                    with _telemetry.span(
                        "reduce_blocks_stream.fold", kind="stage"
                    ):
                        partials = [_combine(partials)]
                elif fold_every is None and len(partials) > 1:
                    # no tree-fold will ever drain this list: spill the
                    # PREVIOUS chunk's (already computed) partial to
                    # host so unfoldable streams cost O(#chunks) host
                    # RAM — the documented bound — not device HBM. The
                    # newest partial stays on device, so the current
                    # dispatch still overlaps the next chunk's
                    # production/transfer. The spill is a real D2H sync
                    # and is accounted as one (host_sync span/counter +
                    # d2h bytes) — diagnostics previously
                    # under-reported D2H traffic on long unfoldable
                    # streams.
                    partials[-2] = _spill_partial_to_host(
                        partials[-2], len(partials) - 2
                    )
            if ckpt is not None:
                # the commit point: chunk `ordinal - 1` is fully folded
                # into `partials`, so (ordinal, partials) is exactly the
                # state an uninterrupted run holds here
                ckpt.note_chunk_folded(ordinal, partials)
        # drain the double-buffer slots into the final combine (at most
        # two running folds — each already the eager reduction of its
        # half of the stream)
        partials.extend(d for d in dbuf if d is not None)
        if not partials:
            raise ValueError(
                "reduce_blocks_stream over an empty iterator (or every "
                "chunk had zero rows)"
            )
        if len(partials) == 1:
            out = partials[0]
        else:
            with _telemetry.span("reduce_blocks_stream.fold", kind="stage"):
                out = _combine(partials)
    except (DeadlineExceeded, Cancelled) as e:
        # clean cooperative exits commit the progress so far — the
        # budget bought (ordinal - watermark) folded chunks; a resume
        # picks up from the committed watermark instead of chunk zero
        if ckpt is not None:
            ckpt.on_interrupt(e, ordinal, partials)
        raise
    if ckpt is not None:
        # completion commit: watermark = every chunk, so an identical
        # re-run resumes to a no-op (restored partials combine; zero
        # chunks re-decode)
        ckpt.finalize(ordinal, partials)
    if len(fetch_list) == 1:
        return out[_base(fetch_list[0])]
    return out


