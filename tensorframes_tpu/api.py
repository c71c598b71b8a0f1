"""The five execution verbs + schema utilities: the public API.

TPU-native implementation of the reference's `OperationsInterface`
(`Operations.scala:20-135`) and Python surface (`core.py`):

- ``map_blocks(fetches, frame, trim=...)``   (`Operations.scala:43,59`)
- ``map_rows(fetches, frame)``               (`Operations.scala:77`)
- ``reduce_rows(fetches, frame)``            (`Operations.scala:96`)
- ``reduce_blocks(fetches, frame)``          (`Operations.scala:108`)
- ``aggregate(fetches, frame.group_by(k))``  (`Operations.scala:126`)
- ``analyze`` / ``print_schema`` / ``append_shape`` (`ExperimentalOperations.scala`)
- ``block`` / ``row`` placeholder helpers    (`core.py:451-474`)

Graphs may be builder-DSL tensors, imported GraphDefs (bytes / file path /
`Graph`), or plain Python functions over column arrays (the TPU-native
tracer front-end — no GraphDef needed).

Execution model vs the reference: instead of one native TF session per
Spark partition (`performMap`, `DebugRowOps.scala:773-810`), each graph is
jitted once into an XLA executable and applied per block; reductions stack
per-block partials and run one combine step (the driver-funneled pairwise
`RDD.reduce` at `DebugRowOps.scala:507,530` becomes a single on-device
fold — distributed variants ride ICI collectives, see `parallel/`).

Validation mirrors `SchemaTransforms` (`DebugRowOps.scala:80-272`): dtype
equality (TF graphs don't promote), column shapes must be at least as
precise as placeholder shapes (else the error points at `analyze`), and
the reduce verbs enforce the reference's naming conventions
(``x`` ↔ ``x_input`` for block reduces, ``x`` ↔ ``x_1``/``x_2`` for row
reduces, `DebugRowOps.scala:80-262`).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax import lax

from .frame import Column, TensorFrame, factorize_keys
from .graph import builder as dsl
from .graph.analysis import GraphSummary, ShapeHints, analyze_graph
from .graph.ir import Graph, base_name, parse_edge
from .ops.lowering import build_callable
from .runtime import deadline as _dl
from .runtime.deadline import deadline_entry as _deadline_entry
from .runtime.executor import Executor, default_executor
from .runtime.faults import maybe_check_numerics
from .schema import Shape
from .utils import telemetry as _tele

__all__ = [
    "map_blocks",
    "map_rows",
    "reduce_blocks",
    "reduce_rows",
    "reduce_blocks_stream",
    "aggregate",
    "analyze",
    "print_schema",
    "append_shape",
    "block",
    "row",
    "group_by",
    "GroupedFrame",
    "explain",
    "explain_detailed",
    "block_to_row",
    "lazy",
    "LazyFrame",
]

Fetches = Union[dsl.Tensor, Sequence[dsl.Tensor], Graph, bytes, str, Callable]


def _is_pandas(obj) -> bool:
    return type(obj).__module__.startswith("pandas")


def _pandas_in_out(verb):
    """Verb wrapper: pandas in/out (the reference's local-debug path,
    `_map_pd`, `core.py:171-183`) + execution stats recording
    (`utils.profiling.record`)."""
    import functools

    from .utils.profiling import record

    @functools.wraps(verb)
    def wrapper(fetches, frame, *args, **kwargs):
        if _is_pandas(frame):
            tf_frame = TensorFrame.from_pandas(frame)
            with record(verb.__name__, tf_frame.nrows):
                out = verb(fetches, tf_frame, *args, **kwargs)
            from .lazy import LazyFrame

            if isinstance(out, LazyFrame):
                # pandas in -> pandas out is the eager debug path; a
                # lazy() mode active around it must not leak a deferred
                # plan to a pandas caller
                out = out.force()
            return out.to_pandas() if isinstance(out, TensorFrame) else out
        rows = frame.nrows if isinstance(frame, TensorFrame) else 0
        with record(verb.__name__, rows):
            return verb(fetches, frame, *args, **kwargs)

    return wrapper


# ---------------------------------------------------------------------------
# graph normalization
# ---------------------------------------------------------------------------


def _as_graph(
    fetches: Fetches, fetch_names: Optional[Sequence[str]]
) -> Tuple[Graph, List[str]]:
    if isinstance(fetches, dsl.Tensor):
        return dsl.build(fetches)
    if isinstance(fetches, (list, tuple)) and all(
        isinstance(f, dsl.Tensor) for f in fetches
    ):
        return dsl.build(list(fetches))
    if isinstance(fetches, Graph):
        g = fetches
    elif isinstance(fetches, bytes):
        g = Graph.from_bytes(fetches)
    elif isinstance(fetches, str):
        g = Graph.from_file(fetches)
    else:
        raise TypeError(f"cannot interpret fetches of type {type(fetches)!r}")
    if not fetch_names:
        raise ValueError(
            "imported graphs need explicit fetch_names=[...] "
            "(the reference's builder.fetches, PythonInterface.scala:105-108)"
        )
    # Control flow (v1 Switch/Merge rings, v2 If/While, function calls)
    # functionalizes to _Cond/_While pseudo-nodes FIRST — the reference
    # could hand any GraphDef to libtensorflow (`TensorFlowOps.scala:76-95`);
    # here the same graphs must become lax.cond/lax.while_loop to compile.
    from .graph.control_flow import functionalize

    g, fetch_names = functionalize(g, list(fetch_names))
    # Stateful graphs are frozen at import, exactly where the reference
    # freezes them (`_get_graph` -> `_initialize_variables`, core.py:42-56).
    from .graph.freeze import freeze_variables

    return freeze_variables(g), list(fetch_names)


_base = base_name


# ---------------------------------------------------------------------------
# placeholder <-> column matching + validation (SchemaTransforms)
# ---------------------------------------------------------------------------

_REDUCE_SUFFIXES = ("_input", "_1", "_2")


def _default_column(ph_name: str, frame: TensorFrame) -> str:
    """Reference naming conventions: placeholder ``x_input``/``x_1``/``x_2``
    reads column ``x`` by default (`DebugRowOps.scala:80-262`). An exact
    column-name match always wins — suffix stripping only kicks in when no
    column carries the placeholder's literal name (so a column named
    ``temp_1`` is not hijacked by the convention)."""
    if ph_name in frame.info:
        return ph_name
    for suf in _REDUCE_SUFFIXES:
        if ph_name.endswith(suf):
            candidate = ph_name[: -len(suf)]
            if candidate in frame.info:
                return candidate
    return ph_name


def _check_bindings(
    summary: GraphSummary, bindings: Dict[str, "np.ndarray"]
) -> None:
    """Validate per-call bound arrays against their placeholders.

    Bindings are the TPU-native answer to the reference's pattern of
    re-embedding updated values as graph constants each iteration (e.g.
    `kmeans_demo.py` rebuilds the graph with new centers every Lloyd step,
    which under XLA would force a recompile per step): a bound array is a
    *jit argument*, so the compiled executable is reused across calls as
    long as the shape is stable."""
    from .schema import ScalarType

    for name, arr in bindings.items():
        if name not in summary.inputs:
            raise ValueError(
                f"binding {name!r} does not match any placeholder "
                f"(placeholders: {sorted(summary.inputs)})"
            )
        ph = summary.inputs[name]
        st = ScalarType.from_np_dtype(np.dtype(arr.dtype))
        if st is not ph.dtype:
            raise ValueError(
                f"binding {name!r} has dtype {st.name} but placeholder wants "
                f"{ph.dtype.name} (TF graphs do not promote dtypes)"
            )
        if not Shape(arr.shape).check_more_precise_than(ph.shape):
            raise ValueError(
                f"binding {name!r} with shape {tuple(arr.shape)} is not "
                f"compatible with placeholder shape {ph.shape}"
            )


def _host_or_device(bindings: Optional[Dict]) -> Dict:
    """A graph's bound arrays: a `jax.Array` stays on its device, anything
    else becomes a host array (one array a placeholder)."""
    return {
        k: v if isinstance(v, jax.Array) else np.asarray(v)
        for k, v in (bindings or {}).items()
    }


def _match_columns(
    summary: GraphSummary,
    frame: TensorFrame,
    feed_dict: Optional[Dict[str, str]],
    block_level: bool,
    bindings: Optional[Dict[str, "np.ndarray"]] = None,
) -> Dict[str, str]:
    """Map placeholder name -> column name; validate dtype + shape precision.

    Placeholders named in ``bindings`` are fed the bound array per call
    instead of a column and are excluded from the mapping."""
    feed_dict = feed_dict or {}
    mapping: Dict[str, str] = {}
    for ph_name, ph in summary.inputs.items():
        if bindings and ph_name in bindings:
            continue
        col_name = feed_dict.get(ph_name, _default_column(ph_name, frame))
        if col_name not in frame.info:
            raise ValueError(
                f"placeholder {ph_name!r} wants column {col_name!r} which is "
                f"not in the frame (columns: {frame.columns}); use feed_dict "
                "to rename"
            )
        info = frame.info[col_name]
        if info.dtype is not ph.dtype:
            raise ValueError(
                f"placeholder {ph_name!r} has dtype {ph.dtype.name} but "
                f"column {col_name!r} has dtype {info.dtype.name} (TF graphs "
                "do not promote dtypes)"
            )
        col_shape = info.block_shape if block_level else info.cell_shape
        if not col_shape.check_more_precise_than(ph.shape):
            raise ValueError(
                f"column {col_name!r} with shape {col_shape} is not compatible"
                f" with shape {ph.shape} requested by placeholder {ph_name!r}."
                " If the column shape has unknown dims, run tfs.analyze(frame)"
                " first (ExperimentalOperations.analyze)"
            )
        mapping[ph_name] = col_name
    return mapping


def _require_dense(frame: TensorFrame, cols: Sequence[str], verb: str) -> None:
    for c in cols:
        if not frame.column(c).is_dense:
            raise ValueError(
                f"{verb}: column {c!r} is ragged (rows have varying shapes); "
                "block-level ops need uniform cells — use map_rows, or fix "
                "the data"
            )


def _ph_overrides(
    summary_graph: Graph,
    frame: TensorFrame,
    feed_dict: Optional[Dict[str, str]],
    block_level: bool,
    bindings: Optional[Dict[str, "np.ndarray"]] = None,
) -> Dict[str, Shape]:
    """Column shapes are usually *more* precise than placeholder attrs
    (e.g. imported graphs carry [?,?]); inject them for tighter analysis,
    mirroring how `block()` stamps column shapes onto placeholders
    (`DslImpl.scala:90-107`)."""
    feed_dict = feed_dict or {}
    bindings = bindings or {}
    overrides: Dict[str, Shape] = {}
    for ph in summary_graph.placeholders():
        if ph.name in bindings:
            shape = Shape(np.asarray(bindings[ph.name]).shape)
            attr = ph.shape_attr
            # Only overriding when compatible (same guard as the column
            # path below) keeps the declared placeholder shape visible to
            # _check_bindings for incompatible bindings.
            if attr is None or shape.check_more_precise_than(attr):
                overrides[ph.name] = shape
            continue
        col_name = feed_dict.get(ph.name, _default_column(ph.name, frame))
        if col_name in frame.info:
            info = frame.info[col_name]
            shape = info.block_shape if block_level else info.cell_shape
            attr = ph.shape_attr
            if attr is None or shape.check_more_precise_than(attr):
                overrides[ph.name] = shape
    return overrides


# ---------------------------------------------------------------------------
# output frame assembly
# ---------------------------------------------------------------------------


_donation_warning_filtered = False
_donation_filter_lock = threading.Lock()


def _quiet_donation_warning() -> None:
    """Register (once, process-wide) an ignore filter for jax's "Some
    donated buffers were not usable" warning: a reduce's output is
    smaller than its stacked partials by construction, so most donated
    partial buffers are freed for intermediate reuse rather than
    aliased into the output — exactly the intent, not a bug worth
    warning about. One-time registration (module-level lock) instead of
    a per-call ``warnings.catch_warnings`` because the latter mutates
    and restores process-global filter state and is not thread-safe
    under concurrent verbs."""
    global _donation_warning_filtered
    if _donation_warning_filtered:
        return
    import warnings

    with _donation_filter_lock:
        if not _donation_warning_filtered:
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            _donation_warning_filtered = True


def _dispatch_reduce_block(
    span_name, fp, fn, mask_plan, sched, fscope, bi, lo, hi,
    feeds_for, split_combs, what_verb,
):
    """One reduce-block dispatch with classified fault handling — THE
    shared recipe of the eager reduce and the fused lazy reduce
    terminal. Transient errors retry with backoff (+ device failover
    under the scheduler, via ``fscope``); a RESOURCE error (OOM)
    splits ``[lo, hi)`` in half down the bucket ladder and
    monoid-combines the half partials (`faults.combine_split_partials`)
    when ``split_combs`` — the chunk-classifier verdict, in fetch
    order — proves the graph combinable; unclassifiable graphs
    re-raise the original error exactly. Returns the partial tuple."""
    from . import shape_policy as _sp
    from .runtime import faults as _flt

    def run(lo_, hi_, depth):
        feeds = feeds_for(lo_, hi_)
        bucket = None
        if mask_plan is not None:
            # pad ONCE per logical dispatch, OUTSIDE the retried thunk
            # (the same discipline as the map paths): a transient
            # retry re-dispatches the already-padded feeds instead of
            # re-padding, and pad_feeds' bucket_fill observation fires
            # exactly once per logical dispatch, not once per attempt
            feeds, bucket = _sp.pad_feeds(feeds, hi_ - lo_)

        def _thunk():
            # per-attempt span: retried/failed-over attempts each
            # charge the device they actually dispatched to; a masked
            # dispatch labels its bucket rung (the dispatched lead
            # dim) so pad waste and the ledger-shape join see it
            with _tele.dispatch_span(
                span_name, program=fp, block=bi, rows=hi_ - lo_,
                bucket=bucket,
                masked=mask_plan is not None or None,
                device=sched.label(bi) if sched is not None else None,
            ):
                if mask_plan is not None:
                    if sched is not None:
                        return sched.bind(bi, fn, valid=hi_ - lo_)(*feeds)
                    return fn(np.int32(hi_ - lo_), *feeds)
                if sched is not None:
                    return sched.bind(bi, fn)(*feeds)
                return fn(*feeds)

        try:
            outs = fscope.dispatch(
                _thunk,
                what=f"{what_verb} block {bi} rows [{lo_}:{hi_})",
                sched=sched, index=bi,
            )
        except Exception as e:
            if _flt.classify(e) != _flt.RESOURCE:
                raise
            if split_combs is None:
                # OOM on an unclassifiable reduce: no monoid recipe to
                # combine halves — re-raise the original error, with
                # the forensic snapshot explaining WHY no split ran
                _flt.record_oom(
                    what_verb, fp, hi_ - lo_, depth,
                    "reraise:unclassifiable-reduce", e, bucket=bucket,
                )
                raise
            if not _flt.split_allowed(hi_ - lo_, depth):
                _flt.record_oom(
                    what_verb, fp, hi_ - lo_, depth,
                    "reraise:split-depth-exhausted", e, bucket=bucket,
                )
                raise
            mid = (lo_ + hi_) // 2
            _flt.record_oom(
                what_verb, fp, hi_ - lo_, depth,
                f"split:[{lo_}:{mid})+[{mid}:{hi_})", e, bucket=bucket,
            )
            _flt.note_split(what_verb)
            left = run(lo_, mid, depth + 1)
            right = run(mid, hi_, depth + 1)
            return _flt.combine_split_partials(
                split_combs, left, right, mid - lo_, hi_ - mid
            )
        return tuple(outs)

    return run(lo, hi, 0)


def _combine_partials(ex, kind, graph, fetch_list, feed_names, build, partials):
    """One jitted donated combine over all per-block partials — the ONE
    donation/caching discipline both reduce verbs share.

    The partials arrive as a tuple of per-block fetch tuples of device
    arrays (never host-fetched); ``build()`` returns the combine
    function of that parts-pytree (stack on device, re-reduce — the
    stacking recipe differs between reduce_blocks' re-fed graph and
    reduce_rows' scan fold, which is why it is a parameter). On
    executors that support it the partial buffers are DONATED — after
    the combine they are dead by construction, so XLA reuses their HBM
    for the stacked intermediate instead of allocating fresh buffers.
    """

    def make():
        combine = build()
        if getattr(ex, "supports_donation", False):
            _quiet_donation_warning()
            return jax.jit(combine, donate_argnums=0)
        return jax.jit(combine)

    # cooperative deadline boundary: a verb whose budget ran out during
    # the per-block dispatches must not start the combine
    _dl.check(kind)
    cfn = ex.cached(kind, graph, fetch_list, feed_names, make)
    from .runtime import faults as _flt

    # rows stays unset: the combine consumes per-block PARTIALS, and a
    # partial count in the block_rows histogram would skew the per-block
    # row-size distribution the histogram documents
    with _tele.dispatch_span(
        kind, program=graph.fingerprint(), partials=len(partials)
    ):
        # Classified transient retry — with a donation caveat: on
        # donating executors a failure INSIDE the compiled call may
        # have consumed the partial buffers already, in which case the
        # retry dies on deleted arrays. That secondary error must not
        # mask the real one, so the ORIGINAL transient error re-raises
        # whenever the retry fails differently. (Injected faults raise
        # before the program runs, so their retries do recover.) No
        # split handler here — partials are already reduced, there is
        # no row range to halve — so resource errors surface
        # immediately.
        from . import config as _config

        try:
            return tuple(cfn(tuple(partials)))
        except Exception as first:
            attempts = _config.get().block_retry_attempts
            if _flt.classify(first) != _flt.TRANSIENT or attempts < 1:
                # attempts=0 means retries are OFF — the config contract
                # every FaultScope site honors applies here too
                raise
            _flt.note_transient_retry()
            try:
                return tuple(
                    _flt.run_with_retries(
                        cfn, tuple(partials),
                        attempts=attempts - 1,
                        what=f"{kind} combine", verb=kind,
                    )
                )
            except Exception as second:
                raise first from second


def _assoc_reduce(graph, fetch_list, summary) -> bool:
    """True when re-feeding partials through ``graph`` is an associative
    monoid combine (sum/min/max/prod consuming its placeholder
    DIRECTLY) — the class whose partials may fold hierarchically. Mean
    and transform-then-reduce graphs re-weight/re-apply under nesting
    (the same gate `reduce_blocks_stream` uses before tree-folding)."""
    from .aggregate import _chunk_combiners

    comb = _chunk_combiners(graph, fetch_list, summary, require_direct=True)
    return comb is not None and "mean" not in comb.values()


def _combine_partials_scheduled(
    ex, kind, graph, fetch_list, feed_names, build, partials, owners,
    sched, assoc,
):
    """Combine per-block partials under the block scheduler.

    ``assoc`` graphs (see `_assoc_reduce`) fold each device's partials
    LOCALLY first, then one final cross-device combine over the
    per-device results on the anchor device — transfer volume O(ndev)
    instead of O(blocks), and every step is an async device op (host
    syncs do not grow). Results are bit-identical for min/max under any
    grouping; float sum stays within the documented reassociation
    tolerance. Non-associative graphs (mean, transform-then-reduce,
    unclassified — and reduce_rows folds, whose left-fold-in-block-order
    contract admits no regrouping) gather ALL partials onto the anchor
    (async D2D) and run the single combine in block order, bit-identical
    to the unscheduled verb."""
    groups: Dict[int, List[Tuple]] = {}
    for p, o in zip(partials, owners):
        groups.setdefault(o, []).append(p)
    anchor = sched.anchor_device()
    if assoc and len(groups) > 1:
        stage: List[Tuple] = []
        for slot in sorted(groups):
            parts = groups[slot]
            stage.append(
                parts[0]
                if len(parts) == 1
                else _combine_partials(
                    ex, kind, graph, fetch_list, feed_names, build, parts
                )
            )
        moved = [
            tuple(jax.device_put(x, anchor) for x in p) for p in stage
        ]
        return _combine_partials(
            ex, kind, graph, fetch_list, feed_names, build, moved
        )
    # gather unconditionally, not only when owners span several slots: a
    # reduce_rows single-row partial is a column SLICE whose actual
    # device is the column's home, not its nominal slot, so owners alone
    # cannot prove colocation (device_put to the current device is free)
    partials = [
        tuple(jax.device_put(x, anchor) for x in p) for p in partials
    ]
    return _combine_partials(
        ex, kind, graph, fetch_list, feed_names, build, partials
    )


def _colocate_parts(parts: List, anchor=None) -> List:
    """Move parts spanning several devices onto one anchor device so a
    single jnp op can consume them (jax refuses committed arrays from
    different devices in one computation). The block scheduler's spread
    map outputs and stream partials hit this (a call planned at home,
    `runtime.scheduler`, has its parts on the home device already: they
    stay there, whatever the anchor, so its output lives with the input
    columns it is appended to); everything is `device_put`
    (async D2D/H2D) — no host sync. The puts are ONE span a call,
    ``frame.gather`` (kind ``transfer``: the anchor, the parts, how many
    moved and their bytes), and the counters ``scheduler.bytes_back`` /
    ``scheduler.gather_seconds``; parts already on one device open
    nothing.

    ``anchor`` (a jax device) is the scheduler's anchor: scheduled verbs
    MUST pass it so every call that spread over the same device set
    commits its output to the SAME device — per-call anchors (e.g.
    most-rows) would leave one frame's columns committed to different
    devices, and any
    later dispatch feeding two such columns into one jit call (the
    segment-plan aggregate, or any verb after turning the scheduler
    off) would crash on jax's incompatible-devices check. (Chaining
    verbs with *different* explicit ``devices=`` pins still produces
    mixed commitments — that is the user's deliberate placement, see
    ARCHITECTURE.md "Output coherence".) Without an anchor (unscheduled
    callers over user-mixed inputs), the device already holding the
    most rows wins (first seen breaks ties), minimizing transfer."""
    weight: Dict = {}
    devs: List = []
    for p in parts:
        d = None
        if isinstance(p, jax.Array):
            try:
                ds = p.devices()
                d = next(iter(ds)) if len(ds) == 1 else None
            except Exception:
                d = None
        devs.append(d)
        if d is not None:
            rows = p.shape[0] if getattr(p, "ndim", 0) else 1
            weight[d] = weight.get(d, 0) + rows
    if len(weight) <= 1:
        return list(parts)
    if anchor is None:
        anchor = max(weight.items(), key=lambda kv: kv[1])[0]
    from .runtime.scheduler import device_label

    moved = [p for p, d in zip(parts, devs) if d is not anchor]
    nbytes = sum(getattr(p, "nbytes", 0) for p in moved)
    # ONE span a gather, however many parts: the way back's host time
    # and bytes, apart from the concatenate that follows
    gather = _tele.span(
        "frame.gather", kind="transfer", anchor=device_label(anchor),
        parts=len(parts), moved_parts=len(moved), bytes=nbytes,
    )
    t0 = time.perf_counter()
    with gather:
        out = [
            p if d is anchor else jax.device_put(p, anchor)
            for p, d in zip(parts, devs)
        ]
    # the span's own clock, so the counter and the span agree; the
    # counters are live with telemetry off, when there is no span
    seconds = getattr(gather, "seconds", None)
    if seconds is None:
        seconds = time.perf_counter() - t0
    _tele.counter_inc("scheduler.bytes_back", float(nbytes))
    _tele.counter_inc("scheduler.gather_seconds", seconds)
    return out


def _concat_parts(parts: List, anchor=None) -> "np.ndarray":
    """Concatenate block outputs, staying on device when the parts are
    device arrays (no host round-trip for device-resident frames;
    cross-device parts converge via `_colocate_parts` first — scheduled
    callers pass their schedule's anchor device)."""
    if len(parts) == 1:
        return parts[0]
    with _tele.span("frame.concat", parts=len(parts)):
        if any(isinstance(p, jax.Array) for p in parts):
            import jax.numpy as jnp

            return jnp.concatenate(
                [jnp.asarray(p) for p in _colocate_parts(parts, anchor)]
            )
        return np.concatenate(parts)


def _stack_parts(parts: List, anchor=None) -> "np.ndarray":
    """Stack partials: on device when any is a `jax.Array` (cross-device
    partials converge via `_colocate_parts` first), else with host
    numpy. The host branch matters beyond convenience — for
    native-executor partials (host numpy), a `jnp.stack` would
    initialize the in-process JAX backend next to a native host that
    may own the same device (the double-client hazard `NativeExecutor`
    documents)."""
    if any(isinstance(p, jax.Array) for p in parts):
        import jax.numpy as jnp

        return jnp.stack(
            [jnp.asarray(p) for p in _colocate_parts(parts, anchor)]
        )
    return np.stack([np.asarray(p) for p in parts])


def _empty_output(summary: GraphSummary, base: str, drop_lead: bool) -> np.ndarray:
    """Zero-row array for a graph output over an all-empty frame.

    Closes the reference's standing empty-partition TODO
    (`DebugRowOps.scala:386-387,496,520`): unknown trailing dims collapse
    to 0 (there are no rows to disagree with) and the dtype comes from the
    graph analysis rather than defaulting to float64."""
    info = summary.outputs[base]
    dims = info.shape.dims[1:] if drop_lead else info.shape.dims
    shape = (0,) + tuple(0 if d is None else d for d in dims)
    return np.zeros(shape, dtype=info.dtype.np_dtype)


# _empty_fn_outputs lives in fn_frontend.py (re-exported below)


def _output_frame(
    frame: TensorFrame,
    out_cols: List[Column],
    append_input: bool,
    offsets: Optional[List[int]] = None,
) -> TensorFrame:
    """TF output columns first, sorted by name, then passthrough input
    columns (`DebugRowOps.scala:355,375-379`). On a name collision the graph
    output wins (the frame analogue of SQL duplicate columns)."""
    out_cols = sorted(out_cols, key=lambda c: c.name)
    cols = list(out_cols)
    if append_input:
        shadow = {c.name for c in out_cols}
        cols += [frame.column(n) for n in frame.columns if n not in shadow]
    return TensorFrame(cols, offsets if offsets is not None else frame.offsets)


# ---------------------------------------------------------------------------
# function front-end: trace a Python fn over named column arrays
# ---------------------------------------------------------------------------


# _fn_feed_columns/_fn_outputs_to_dict live in fn_frontend.py


# ---------------------------------------------------------------------------
# bytes/string cells: identity pass-through (the reference's Binary scope)
# ---------------------------------------------------------------------------


def _split_string_passthrough(
    graph: Graph, fetch_list: List[str]
) -> Tuple[Graph, List[str], Dict[str, str]]:
    """Partition fetches into device fetches and bytes pass-throughs.

    The reference supports Binary cells at exactly one scope: a single
    scalar cell carried through the conversion path, never computed on
    (`datatypes.scala:577-581`). Mirrored here: a fetch whose node is an
    Identity-chain over a string placeholder becomes a host-side cell
    copy; any fetch that COMPUTES on string data raises. Returns the
    device-only subgraph, the device fetches, and
    ``{fetch base -> string placeholder name}``.
    """
    from .schema import ScalarType

    str_phs = {
        ph.name
        for ph in graph.placeholders()
        if ph.dtype_attr is ScalarType.string
    }
    if not str_phs:
        return graph, fetch_list, {}
    passthrough: Dict[str, str] = {}
    device_fetches: List[str] = []
    for f in fetch_list:
        cur = _base(f)
        ph = None
        while True:
            node = graph[cur]
            if node.op in ("Placeholder", "PlaceholderV2"):
                ph = node.name if node.name in str_phs else None
                break
            if node.op in ("Identity", "Snapshot", "StopGradient"):
                cur = node.data_inputs()[0][0]
                continue
            break
        if ph is not None:
            passthrough[_base(f)] = ph
        else:
            device_fetches.append(f)
    if device_fetches:
        keep = {n.name for n in graph.toposort(device_fetches)}
        touched = keep & str_phs
        if touched:
            raise ValueError(
                f"fetches {sorted(_base(f) for f in device_fetches)} compute "
                f"on bytes-column data (via {sorted(touched)}); bytes cells "
                "support identity pass-through only (the reference's "
                "one-scalar-cell Binary scope, datatypes.scala:577-581)"
            )
        dev_graph = Graph([n for n in graph.nodes if n.name in keep])
    else:
        dev_graph = Graph([])
    return dev_graph, device_fetches, passthrough


def _string_passthrough_columns(
    passthrough: Dict[str, str],
    frame: TensorFrame,
    feed_dict: Optional[Dict[str, str]],
) -> List[Column]:
    """Resolve + validate the bytes columns and copy their cells."""
    from .schema import ScalarType

    feed_dict = feed_dict or {}
    cols = []
    for base, ph in passthrough.items():
        col_name = feed_dict.get(ph, _default_column(ph, frame))
        if col_name not in frame.info:
            raise ValueError(
                f"placeholder {ph!r} wants column {col_name!r} which is not "
                f"in the frame (columns: {frame.columns})"
            )
        info = frame.info[col_name]
        if info.dtype is not ScalarType.string:
            raise ValueError(
                f"placeholder {ph!r} is a bytes placeholder but column "
                f"{col_name!r} has dtype {info.dtype.name}"
            )
        if info.cell_shape.rank != 0:
            raise ValueError(
                f"bytes column {col_name!r} must hold one scalar cell per "
                "row (the reference's Binary scope, datatypes.scala:577-581)"
            )
        cols.append(
            Column(base, list(frame.column(col_name).rows()), ScalarType.string)
        )
    return cols


# ---------------------------------------------------------------------------
# the block loop: one dispatch per non-empty block, shared by every
# per-block map (graph or plain function, map_blocks or dense map_rows)
# ---------------------------------------------------------------------------


def _place_bindings(bindings: Dict, sched, ex) -> Dict:
    """The call's bound values on the devices its schedule dispatches
    to (`runtime.bindings`): device leaves once, host leaves once a call."""
    from .runtime import bindings as _rb

    if not bindings:
        return {}
    devices = None
    if sched is not None:
        devices = [
            sched.devices[s] for s in sorted(
                {s for s in sched.assignment if s is not None}
            )
        ]
    return _rb.place(
        bindings, devices,
        on_device=getattr(ex, "supports_scheduling", False),
    )


def _run_blocks(
    verb: str,
    frame: TensorFrame,
    fn: Callable,
    fp: str,
    feed_names: Sequence[str],
    columns: Dict[str, object],
    bound: Dict,
    out_names: Optional[Sequence[str]],
    sched,
    trim: bool = False,
    rowwise: bool = False,
    bucketed: bool = False,
    empty: Optional[Callable[[], Dict]] = None,
) -> Tuple[List[Column], List[int]]:
    """Dispatch ``fn`` over every non-empty block of ``frame`` and
    concatenate what the blocks give: ``(output columns, offsets)``.

    ``feed_names`` orders the program's arguments: a name in ``columns``
    is fed the block's rows of that column's values, a name in ``bound``
    the bound tree whole (`_place_bindings`). ``fn`` returns one output
    per name of ``out_names``, or a dict (a plain function: the names
    are its keys). ``bucketed`` pads the column feeds up the bucket
    ladder, or takes a window of resident columns, and takes the pad
    rows off again (`shape_policy.block_dispatch`, which may run the
    block at its exact shape instead, on ``fn`` or on an executable of
    it); the caller sets
    it only for programs it knows row-local. ``rowwise``
    lets a RESOURCE fault split the block's rows in half, to
    ``config.oom_split_depth``. ``empty()`` names and shapes the
    outputs of a frame with no rows.

    A run of equal blocks is ONE dispatch, a group
    (`shape_policy.group_dispatch`: one pass of the program over the
    run's rows, which is what its blocks give row for row because
    ``bucketed`` programs are row-local), where the call is
    ``bucketed``, nothing is bound or trimmed, the outputs' names are
    known beforehand (a graph's program gives a sequence, a plain
    function a dict), the columns are resident on one device
    (`shape_policy.block_runs`) and there is no schedule, or the
    schedule is a home plan that has every block of the run on that
    device (`BlockSchedule.at_home`). The group runs on the columns
    where they are, so a schedule puts nothing and books the run once
    (`BlockSchedule.note_run`). A frame that is one run has one part
    and no concat. Any other block, and a run whose group ran out of
    memory (one pass holds the program's temporaries at the run's rows)
    or, under a schedule, met a transient fault (the block loop owns
    retry, backoff and failover), goes through the loop one block at a
    time.

    Per dispatch, a block's or a group's: classified fault handling
    (`runtime.faults`: transient errors retry with backoff and fail
    over under the scheduler; the verb's deadline is checked at every
    dispatch), a ``<verb>.block`` dispatch span per attempt labeled
    with the device it ran on (a group's carries ``blocks`` and the
    run's rows), and the numerics check. Spans: ``<verb>.blocks``
    around the loop, ``frame.cut`` / ``shape.pad`` / ``shape.unpad`` /
    ``frame.concat`` where a block is cut, padded or joined."""
    from . import shape_policy as _sp
    from .runtime import faults as _flt

    fscope = _flt.scope(verb)
    col_names = [n for n in feed_names if n in columns]
    col_values = [columns[n] for n in col_names]

    def _dispatch_group(
        bi: int, lo_: int, n: int, k: int, end: int
    ) -> Optional[List]:
        """The outputs over the ``k`` blocks of ``n`` rows from block
        ``bi`` (row ``lo_``) on, from one dispatch; None where the run
        has no group, or its group ran out of memory or, under a
        schedule, met a transient fault."""
        call = _sp.group_dispatch(fn, col_values, lo_, n, k)
        if call is None:
            return None

        def _thunk():
            with _tele.dispatch_span(
                f"{verb}.block", program=fp, block=bi, rows=k * n,
                bucket=k * n, blocks=k,
                device=sched.label(bi) if sched is not None else None,
            ):
                outs = call(*col_values)
            if sched is not None:
                sched.note_run(bi, end)
            return outs

        # under a schedule one attempt: the block loop fails over
        scope = fscope if sched is None else _flt.scope(verb, attempts=0)
        try:
            return list(scope.dispatch(
                _thunk,
                what=f"{verb} blocks [{bi}:{bi + k}) rows "
                f"[{lo_}:{lo_ + k * n})",
                sched=sched,  # no index: a deadline's stamps, no eviction
            ))
        except Exception as e:
            fault = _flt.classify(e)
            if fault == _flt.TRANSIENT and sched is not None:
                return None
            if fault != _flt.RESOURCE:
                raise
            _flt.record_oom(
                verb, fp, k * n, 0, f"split:{k} blocks of {n} rows, one by one",
                e, bucket=k * n,
            )
            _flt.note_split(verb)
            return None

    def _dispatch_rows(bi: int, lo_: int, hi_: int, depth: int) -> List:
        def _cut() -> List:
            if lo_ == 0 and hi_ == frame.nrows:  # the whole frame: no cut
                return list(col_values)
            with _tele.span("frame.cut", block=bi, rows=hi_ - lo_):
                return [v[lo_:hi_] for v in col_values]

        if bucketed:
            # a window of the resident columns where they have one, else
            # the cut: at its exact shape as its rung's first size or on
            # the executable a repeated pad has bought, else padded
            # (`shape_policy.block_dispatch`)
            blk = _sp.block_dispatch(
                fn, col_values, lo_, hi_, _cut,
                sched.device(bi) if sched is not None else None,
            )
            cut, bucket, program = blk.feeds, blk.bucket, blk.call
        else:
            _sp.exact_dispatch()
            blk, cut, bucket, program = None, _cut(), hi_ - lo_, fn
        by_name = dict(zip(col_names, cut))

        def _thunk():
            # span inside the thunk: each ATTEMPT records its own
            # dispatch span labeled with the device it actually ran on
            # (after failover the retry charges the NEW device, and
            # backoff sleeps stay outside dispatch spans)
            device = sched.device(bi) if sched is not None else None
            feeds = [
                by_name[n] if n in by_name else bound[n].on(device)
                for n in feed_names
            ]
            call = sched.bind(bi, program) if sched is not None else program
            with _tele.dispatch_span(
                f"{verb}.block", program=fp, block=bi, rows=hi_ - lo_,
                bucket=bucket if bucketed else None,
                device=sched.label(bi) if sched is not None else None,
            ):
                return call(*feeds)

        try:
            outs = fscope.dispatch(
                _thunk,
                what=f"{verb} block {bi} rows [{lo_}:{hi_})",
                sched=sched, index=bi,
            )
        except Exception as e:
            if _flt.classify(e) != _flt.RESOURCE:
                raise
            verdict = None
            if not rowwise:
                verdict = "reraise:not-row-local"
            elif not _flt.split_allowed(hi_ - lo_, depth):
                verdict = "reraise:split-depth-exhausted"
            mid = (lo_ + hi_) // 2
            _flt.record_oom(
                verb, fp, hi_ - lo_, depth,
                verdict or f"split:[{lo_}:{mid})+[{mid}:{hi_})", e,
                bucket=bucket if bucketed else None,
            )
            if verdict:
                raise
            _flt.note_split(verb)
            left = _dispatch_rows(bi, lo_, mid, depth + 1)
            right = _dispatch_rows(bi, mid, hi_, depth + 1)
            return [_concat_parts([a, b]) for a, b in zip(left, right)]
        if isinstance(outs, dict):  # a plain function names its outputs
            names[:] = names or list(outs)
            outs = [outs[n] for n in names]
        return list(outs) if blk is None else blk.unpad(outs)

    names: List[str] = list(out_names or [])
    acc: List[List] = []
    out_sizes: List[int] = []
    runs: Dict[int, Tuple[int, int, int]] = {}
    if (
        frame.num_blocks > 1 and bucketed
        and not trim and not bound and names
    ):
        runs = _sp.block_runs(col_values, frame.offsets)
    # the device a schedule must have a run's blocks on for a group
    home = (
        _sp._resident_device(col_values)
        if runs and sched is not None else None
    )
    try:
        with _tele.span(f"{verb}.blocks", kind="stage"):
            bi = 0
            while bi < frame.num_blocks:
                lo, hi = frame.offsets[bi], frame.offsets[bi + 1]
                if lo == hi:
                    out_sizes.append(0)
                    bi += 1
                    continue  # empty block: contributes nothing (the reference's
                    # empty-partition TODO, `DebugRowOps.scala:386-387`)
                n, k, end = runs.get(bi, (hi - lo, 1, bi + 1))
                outs = None
                if k > 1 and (sched is None or sched.at_home(bi, end, home)):
                    outs = _dispatch_group(bi, lo, n, k, end)
                if outs is None:  # one block, or of a run with no group
                    k, end = 1, bi + 1
                    outs = _dispatch_rows(bi, lo, hi, 0)
                hi = lo + k * n
                maybe_check_numerics(
                    names, outs,
                    f"{verb} block {bi}" if k == 1 else f"{verb} blocks [{bi}:{end})",
                )
                bsize = None
                for f, o in zip(names, outs):
                    # keep device arrays on device; shape checks are metadata-only
                    if not trim and (o.ndim == 0 or o.shape[0] != hi - lo):
                        raise ValueError(
                            f"{verb}: output {f!r} has lead dim "
                            f"{o.shape[0] if o.ndim else '<scalar>'} but the block "
                            f"has {hi - lo} rows"
                            + ("; use trim=True for row-count-changing maps"
                               if verb == "map_blocks" else "")
                        )
                    if trim:
                        if o.ndim == 0:
                            raise ValueError(
                                f"{verb}(trim): output {f!r} must have a lead dim"
                            )
                        if bsize is None:
                            bsize = o.shape[0]
                        elif o.shape[0] != bsize:
                            raise ValueError(
                                f"{verb}(trim): outputs disagree on row count"
                            )
                acc.append(outs)
                out_sizes.append(bsize)  # read under `trim` only
                bi = end
    finally:
        # a call that ends early (a raised block, a deadline) leaves its
        # books and what it never issued with the counters and the gauge;
        # a whole call's last dispatch has handed them over already
        if sched is not None:
            sched.flush()

    anchor = sched.anchor_device() if sched is not None else None
    if acc:
        out_cols = [
            Column(_base(n), _concat_parts([outs[i] for outs in acc], anchor))
            for i, n in enumerate(names)
        ]
    else:  # every block empty: zero-row outputs
        out_cols = [Column(_base(n), v) for n, v in empty().items()]
    offsets = list(np.cumsum([0] + out_sizes)) if trim else frame.offsets
    return out_cols, offsets


# ---------------------------------------------------------------------------
# map_blocks
# ---------------------------------------------------------------------------


@_pandas_in_out
@_deadline_entry("map_blocks")
def map_blocks(
    fetches: Fetches,
    frame: TensorFrame,
    feed_dict: Optional[Dict[str, str]] = None,
    trim: bool = False,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
    mesh=None,
    bindings: Optional[Dict[str, "np.ndarray"]] = None,
    devices=None,
) -> TensorFrame:
    """Apply a graph to each block; one jitted XLA call per block.

    `DebugRowOps.mapBlocks` (`DebugRowOps.scala:290-400`). With
    ``trim=True`` the row count may change and input columns are dropped
    (`Operations.scala:59-76`). With ``mesh=`` the blocks shard across the
    device mesh (see `parallel.verbs`). ``bindings`` feeds named
    placeholders a per-call array instead of a column — updates between
    calls do NOT recompile (see `_check_bindings`).

    Without a mesh the block scheduler places the blocks
    (`runtime.scheduler`; ``config.block_scheduler``, default auto-on
    when >1 local device): a row-local graph over columns that all live
    on one local device stays on that device (the home plan: a run of
    equal blocks is then one dispatch, and the output lives with its
    input columns); any other call's per-block dispatches spread across
    ``jax.local_devices()``. ``devices=`` spreads the blocks over an
    explicit device list, block for block (one device = pinning);
    mesh= takes precedence.

    On a `LazyFrame` — or on a plain frame under ``with tfs.lazy():``
    with graph fetches (function/``trim``/``bindings`` calls stay
    eager: they cannot be spliced) — the verb DEFERS: it returns a
    `LazyFrame` carrying the chain as one pending fused graph; see
    `tensorframes_tpu.lazy`.
    """
    from .lazy import LazyFrame, lazy_active

    if isinstance(frame, LazyFrame):
        return frame.map_blocks(
            fetches, feed_dict=feed_dict, trim=trim,
            fetch_names=fetch_names, executor=executor, mesh=mesh,
            bindings=bindings, devices=devices,
        )
    from . import globalframe as _gf

    if isinstance(frame, _gf.GlobalFrame):
        # sharded-array frame: ONE SPMD dispatch over its data mesh
        # (mesh=/devices= rejected there — the frame owns placement)
        return _gf.map_blocks_global(
            fetches, frame, feed_dict=feed_dict, trim=trim,
            fetch_names=fetch_names, executor=executor, mesh=mesh,
            bindings=bindings, devices=devices,
        )
    if (
        lazy_active()
        and isinstance(frame, TensorFrame)
        and not trim
        and not bindings
        and not (callable(fetches) and not isinstance(fetches, dsl.Tensor))
    ):
        from .schema import ScalarType

        lazy_graph, lazy_fetches = _as_graph(fetches, fetch_names)
        if not any(
            ph.dtype_attr is ScalarType.string
            for ph in lazy_graph.placeholders()
        ):
            # _fuse_stage directly: the graph is already normalized
            # (functionalized + frozen), and re-running _as_graph on it
            # would pay that pass twice per deferred call
            return LazyFrame(
                frame, executor=executor, mesh=mesh, devices=devices
            )._fuse_stage(
                "map_blocks", lazy_graph, lazy_fetches, feed_dict
            )
        # bytes pass-through cannot splice: stay eager under the mode
        # (the documented contract), falling through to the graph path
    if callable(fetches) and not isinstance(fetches, dsl.Tensor):
        if mesh is not None:
            from .parallel import verbs as _pverbs

            return _pverbs.map_blocks(
                fetches, frame, mesh, feed_dict, trim, fetch_names, executor,
                bindings=bindings,
            )
        return _map_blocks_fn(
            fetches, frame, trim, executor or default_executor(),
            bindings=bindings, devices=devices,
        )
    graph, fetch_list = _as_graph(fetches, fetch_names)
    graph, fetch_list, str_pass = _split_string_passthrough(graph, fetch_list)
    if str_pass:
        # bytes columns ride host-side in every topology: split them off
        # BEFORE the mesh dispatch so mesh= behaves like the local path
        if trim:
            raise ValueError(
                "map_blocks(trim): bytes pass-through requires a "
                "row-preserving map"
            )
        str_cols = _string_passthrough_columns(str_pass, frame, feed_dict)
        if fetch_list:
            dev = map_blocks(
                graph, frame, feed_dict, False, fetch_list, executor,
                mesh=mesh, bindings=bindings, devices=devices,
            )
            dev_cols = [dev.column(_base(f)) for f in fetch_list]
        else:
            if bindings:
                # All fetches were string pass-throughs, so no compute
                # graph runs and no placeholder can consume a binding —
                # a typo'd key must not be dropped on the floor.
                raise ValueError(
                    "map_blocks: bindings "
                    f"{sorted(bindings)} match no placeholder (the "
                    "graph is pure string pass-through)"
                )
            dev_cols = []
        return _output_frame(frame, dev_cols + str_cols, append_input=True)
    if mesh is not None:
        from .parallel import verbs as _pverbs

        return _pverbs.map_blocks(
            graph, frame, mesh, feed_dict, trim, fetch_list, executor,
            bindings=bindings,
        )
    ex = executor or default_executor()
    bindings = _host_or_device(bindings)
    if not trim and not bindings:
        # block_scheduler="global": eligible row-local graphs dispatch
        # as ONE sharded SPMD program instead of one program per block
        routed = _gf.maybe_map_blocks(
            graph, fetch_list, frame, feed_dict, ex, devices
        )
        if routed is not _gf.SKIP:
            return routed
    from . import config as _config
    from . import shape_policy as _sp
    from .runtime import scheduler as _rs

    with _tele.span("map_blocks.plan", kind="stage"):
        with _tele.span("graph.analyze"):
            overrides = _ph_overrides(
                graph, frame, feed_dict, block_level=True, bindings=bindings
            )
            summary = analyze_graph(
                graph, fetch_list, placeholder_shapes=overrides
            )
            _check_bindings(summary, bindings)
        with _tele.span("frame.match"):
            mapping = _match_columns(
                summary, frame, feed_dict, block_level=True,
                bindings=bindings,
            )
            _require_dense(frame, list(mapping.values()), "map_blocks")

        feed_names = sorted(summary.inputs)
        with _tele.span("executor.lookup"):
            fn = ex.callable_for(graph, fetch_list, feed_names)
        # Shape bucketing (`shape_policy`): pad row-local graphs' block
        # feeds up to the bucket ladder and slice the pad rows off every
        # output, so drifting block sizes compile O(log max-rows) jit
        # specializations of this program instead of one per distinct
        # size. trim/bindings/non-rowwise graphs keep the exact per-shape
        # dispatch.
        #
        # the row-local walk feeds bucketing AND OOM split eligibility;
        # with both knobs off it is dead weight on the hot path — skip it
        with _tele.span("shape.classify"):
            rowwise = (
                not trim
                and not bindings
                and (_sp.enabled(ex) or _config.get().oom_split_depth > 0)
                and _sp.rowwise_fetches(
                    graph,
                    fetch_list,
                    {p: ph.shape.rank for p, ph in summary.inputs.items()},
                )
            )
        columns = {n: frame.column(c).values for n, c in mapping.items()}
        with _tele.span("scheduler.plan"):
            # a row-local program stays where its columns are (the home
            # plan, `runtime.scheduler`)
            sched = _rs.schedule_for(
                frame, devices=devices, executor=ex,
                home=(
                    _sp._resident_device(list(columns.values()))
                    if rowwise else None
                ),
            )
        bound = _place_bindings(bindings, sched, ex)

    out_cols, offsets = _run_blocks(
        "map_blocks", frame, fn, graph.fingerprint(), feed_names,
        columns, bound,
        fetch_list, sched, trim=trim, rowwise=rowwise, bucketed=rowwise and _sp.enabled(ex),
        empty=lambda: {
            _base(f): _empty_output(summary, _base(f), drop_lead=True)
            for f in fetch_list
        },
    )
    return _output_frame(frame, out_cols, append_input=not trim, offsets=offsets)


# function front-end kernels + ragged bucketing live in
# fn_frontend.py; re-exported at the end of this module.


# ---------------------------------------------------------------------------
# map_rows
# ---------------------------------------------------------------------------


# ragged bucketing lives in fn_frontend.py (re-exported below)


@_pandas_in_out
@_deadline_entry("map_rows")
def map_rows(
    fetches: Fetches,
    frame: TensorFrame,
    feed_dict: Optional[Dict[str, str]] = None,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
    mesh=None,
    bindings: Optional[Dict[str, "np.ndarray"]] = None,
    devices=None,
) -> TensorFrame:
    """Apply a graph independently to every row.

    `DebugRowOps.mapRows` (`DebugRowOps.scala:403-484`). Dense columns take
    the vmap fast path: the per-row graph is vectorized over the block and
    runs as ONE XLA call per block — versus the reference's one session.run
    per row (`performMapRows`, `DebugRowOps.scala:826-864`). Ragged columns
    fall back to a per-row loop (compile-cached per distinct cell shape),
    the moral equivalent of the reference's variable-length row support
    (`TFDataOps.scala:90-103`). With ``mesh=`` rows shard across the
    device mesh (see `parallel.verbs.map_rows`). ``bindings`` holds
    per-call bound placeholders constant across all rows (vmap
    in_axes=None), the same jit-argument semantics as map_blocks
    bindings.
    """
    from .lazy import LazyFrame

    if isinstance(frame, LazyFrame):
        # terminal in effect: force the fused plan (one program per
        # block), then run the per-row verb on the concrete result
        frame = frame.force()
    from . import globalframe as _gf

    if isinstance(frame, _gf.GlobalFrame):
        # one vmapped SPMD dispatch over the frame's data mesh
        return _gf.map_rows_global(
            fetches, frame, feed_dict=feed_dict, fetch_names=fetch_names,
            executor=executor, mesh=mesh, bindings=bindings,
            devices=devices,
        )
    ex = executor or default_executor()
    if callable(fetches) and not isinstance(fetches, dsl.Tensor):
        if mesh is not None:
            from .parallel import verbs as _pverbs

            return _pverbs.map_rows(
                fetches, frame, mesh, feed_dict, fetch_names, executor,
                bindings=bindings,
            )
        return _map_rows_fn(
            fetches, frame, ex, bindings=bindings, devices=devices
        )
    bindings = _host_or_device(bindings)
    graph, fetch_list = _as_graph(fetches, fetch_names)
    graph, fetch_list, str_pass = _split_string_passthrough(graph, fetch_list)
    if str_pass:
        # bytes columns ride host-side in every topology: split them off
        # BEFORE the mesh dispatch so mesh= behaves like the local path
        str_cols = _string_passthrough_columns(str_pass, frame, feed_dict)
        if fetch_list:
            dev = map_rows(
                graph, frame, feed_dict, fetch_list, executor,
                mesh=mesh, bindings=bindings, devices=devices,
            )
            dev_cols = [dev.column(_base(f)) for f in fetch_list]
        else:
            if bindings:
                # Mirror the map_blocks check: pure string pass-through
                # runs no compute graph, so every binding key is a typo.
                raise ValueError(
                    "map_rows: bindings "
                    f"{sorted(bindings)} match no placeholder (the "
                    "graph is pure string pass-through)"
                )
            dev_cols = []
        return _output_frame(frame, dev_cols + str_cols, append_input=True)
    if mesh is not None:
        from .parallel import verbs as _pverbs

        return _pverbs.map_rows(
            graph, frame, mesh, feed_dict, fetch_list, executor,
            bindings=bindings,
        )
    with _tele.span("map_rows.plan", kind="stage"):
        with _tele.span("graph.analyze"):
            overrides = _ph_overrides(
                graph, frame, feed_dict, block_level=False, bindings=bindings
            )
            summary = analyze_graph(
                graph, fetch_list, placeholder_shapes=overrides
            )
            _check_bindings(summary, bindings)
        with _tele.span("frame.match"):
            mapping = _match_columns(
                summary, frame, feed_dict, block_level=False, bindings=bindings
            )
        params = sorted(summary.inputs)
        col_params = [p for p in params if p not in bindings]
        cols_used = [mapping[p] for p in col_params]
        out_names = [_base(f) for f in fetch_list]
        dense = all(frame.column(c).is_dense for c in cols_used)
        if bindings and not dense:
            raise ValueError(
                "map_rows: bindings are not supported with ragged feed "
                "columns; densify the columns or bake the values as constants"
            )
        if bindings and not col_params:
            raise ValueError(
                "map_rows: every placeholder is bound, so nothing varies per "
                "row; use map_blocks (or run the graph once and broadcast)"
            )

        if dense and not bindings:
            # block_scheduler="global": one vmapped SPMD dispatch instead
            # of one per block (it has no block loop, so it runs, and
            # returns, inside this span)
            routed = _gf.maybe_map_rows(
                graph, fetch_list, frame, feed_dict, ex, devices,
                pre=(summary, mapping),
            )
            if routed is not _gf.SKIP:
                return routed
        if dense:
            in_axes = tuple(None if p in bindings else 0 for p in params)
            bind_sig = ",".join(sorted(bindings))
            with _tele.span("executor.lookup"):
                vfn = ex.cached(
                    f"vmap-rows-[{bind_sig}]" if bindings else "vmap-rows",
                    graph,
                    fetch_list,
                    params,
                    lambda: jax.jit(
                        jax.vmap(
                            build_callable(graph, fetch_list, params),
                            in_axes=in_axes,
                        )
                    ),
                )
            # per-block dispatches spread across local devices like
            # map_blocks; outputs stay device-resident per block and
            # `_concat_parts` concatenates ON DEVICE (colocating
            # cross-device parts), so a chained verb never pays a hidden
            # per-block D2H sync
            from . import shape_policy as _sp
            from .graph import vectorize as _vec
            from .runtime import scheduler as _rs

            with _tele.span("scheduler.plan"):
                sched = _rs.schedule_for(frame, devices=devices, executor=ex)
            bound = _place_bindings(bindings, sched, ex)

    if dense:
        # Bucketed vmapped dispatch (`graph/vectorize.py` companion):
        # the vmapped per-row program is row-independent by
        # construction, so padding a block up the bucket ladder and
        # slicing the pad rows off is always sound, and so is splitting
        # its rows on an OOM. Bindings keep the exact per-shape dispatch.
        out_cols, _ = _run_blocks(
            "map_rows", frame, vfn, graph.fingerprint(), params,
            {p: frame.column(mapping[p]).values for p in col_params}, bound,
            out_names, sched, rowwise=True,
            bucketed=not bindings and _sp.enabled(ex) and _vec.enabled(),
            empty=lambda: {
                n: _empty_output(summary, n, drop_lead=False)
                for n in out_names
            },
        )
    else:
        vfn = ex.cached(
            "vmap-rows",
            graph,
            fetch_list,
            params,
            lambda: jax.jit(
                jax.vmap(build_callable(graph, fetch_list, params))
            ),
        )
        per_out = _run_ragged_bucketed(
            vfn,
            [frame.column(c) for c in cols_used],
            frame.nrows,
            out_names_hint=out_names,
        )
        out_cols = [
            Column(
                n,
                per_out[n]
                if n in per_out
                else _empty_output(summary, n, drop_lead=False),
            )
            for n in out_names
        ]

    return _output_frame(frame, out_cols, append_input=True)


# _map_rows_fn lives in fn_frontend.py (re-exported below)


# ---------------------------------------------------------------------------
# reduce_blocks
# ---------------------------------------------------------------------------


def _validate_reduce_blocks(
    summary: GraphSummary, fetch_list: List[str]
) -> None:
    """`reduceBlocksSchema` naming + shape contract
    (`DebugRowOps.scala:80-170`): output ``x`` ↔ placeholder ``x_input``,
    same dtype, placeholder = output shape + unknown lead dim."""
    allowed = {_base(f) + "_input" for f in fetch_list}
    extra = set(summary.inputs) - allowed
    if extra:
        raise ValueError(
            f"reduce_blocks: placeholders {sorted(extra)} do not follow the "
            f"x -> x_input convention for outputs {sorted(allowed)} "
            "(every input must be re-fed a partial during the combine step)"
        )
    for f in fetch_list:
        base = _base(f)
        ph_name = base + "_input"
        if ph_name not in summary.inputs:
            raise ValueError(
                f"reduce_blocks: output {base!r} requires a placeholder "
                f"named {ph_name!r} (inputs: {sorted(summary.inputs)})"
            )
        ph = summary.inputs[ph_name]
        out = summary.outputs[base]
        if ph.dtype is not out.dtype:
            raise ValueError(
                f"reduce_blocks: {base!r} has dtype {out.dtype.name} but "
                f"{ph_name!r} has dtype {ph.dtype.name}"
            )
        if ph.shape.rank != out.shape.rank + 1:
            raise ValueError(
                f"reduce_blocks: placeholder {ph_name!r} (shape {ph.shape}) "
                f"must be output {base!r} (shape {out.shape}) plus a lead "
                "block dim"
            )
        if not out.shape.check_more_precise_than(ph.shape.tail):
            raise ValueError(
                f"reduce_blocks: output {base!r} shape {out.shape} does not "
                f"match placeholder cell shape {ph.shape.tail}; partials "
                "must be re-feedable for the combine step"
            )


@_pandas_in_out
@_deadline_entry("reduce_blocks")
def reduce_blocks(
    fetches: Fetches,
    frame: TensorFrame,
    feed_dict: Optional[Dict[str, str]] = None,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
    mesh=None,
    devices=None,
):
    """Per-block reduce, then one on-device combine over stacked partials.

    `DebugRowOps.reduceBlocks` (`DebugRowOps.scala:510-533`). The reference
    funnels partials to the driver and merges PAIRWISE, each pair a fresh
    session on a 2-row block (`reducePairBlock`, `:748-757`); since the
    contract already demands associativity (Spark `RDD.reduce`), we stack
    all partials into one (num_blocks)-row block and run the same graph
    once. Returns a single array for one fetch, a dict for several
    (`_unpack_row`, `core.py:111-125`).

    Execution is fully async and device-resident: all block dispatches
    are issued before anything is fetched, partials stay in device
    memory, and the combine donates their buffers. The result is a
    device array (`jax.Array` on the in-process executor) — apply
    ``np.asarray`` (or keep chaining) at the boundary you choose.

    On a `LazyFrame` this is a TERMINAL action: the reduce's per-block
    stage fuses into the pending map chain and the whole pipeline runs
    as ONE program per block (see `tensorframes_tpu.lazy`).
    """
    from .lazy import LazyFrame

    if isinstance(frame, LazyFrame):
        return frame.reduce_blocks(
            fetches, feed_dict, fetch_names, executor, mesh, devices=devices
        )
    from . import globalframe as _gf

    if isinstance(frame, _gf.GlobalFrame):
        # ONE masked SPMD dispatch; classified reductions lower to
        # in-program collectives over the frame's data mesh
        return _gf.reduce_blocks_global(
            fetches, frame, feed_dict=feed_dict, fetch_names=fetch_names,
            executor=executor, mesh=mesh, devices=devices,
        )
    if mesh is not None:
        from .parallel import verbs as _pverbs

        return _pverbs.reduce_blocks(
            fetches, frame, mesh, feed_dict, fetch_names, executor
        )
    ex = executor or default_executor()
    graph, fetch_list = _as_graph(fetches, fetch_names)
    # block_scheduler="global": classified monoid reduces dispatch as
    # one sharded program with in-program collectives
    routed = _gf.maybe_reduce_blocks(
        graph, fetch_list, frame, feed_dict, ex, devices
    )
    if routed is not _gf.SKIP:
        return routed
    overrides = _ph_overrides(graph, frame, feed_dict, block_level=True)
    summary = analyze_graph(graph, fetch_list, placeholder_shapes=overrides)
    _validate_reduce_blocks(summary, fetch_list)
    mapping = _match_columns(summary, frame, feed_dict, block_level=True)
    _require_dense(frame, list(mapping.values()), "reduce_blocks")

    feed_names = sorted(summary.inputs)
    # Shape bucketing: graphs the chunk classifier proves to be monoid
    # reduces over row-local transforms run a MASKED bucketed program
    # ("block-bucketed" kind) — block feeds pad to the bucket ladder and
    # pad rows mask to the reduction identity at the transform output,
    # so drifting block sizes compile O(log max-rows) programs. The
    # `valid` row count rides as a traced scalar (no respecialization
    # within a bucket). Unclassifiable graphs keep the exact program.
    from . import shape_policy as _sp

    # one classification serves the masked bucketed program AND the
    # OOM split-retry combine recipe (`faults.combine_split_partials`):
    # the mask plan already carries the fetch-ordered combiner verdicts,
    # so the walk runs at most once per call — and not at all when both
    # bucketing and splitting are off. split_combs=None means a
    # resource failure re-raises exactly instead of splitting.
    from . import config as _config

    mask_plan = (
        _sp.masked_reduce_plan(graph, fetch_list, summary)
        if _sp.enabled(ex)
        else None
    )
    if mask_plan is not None:
        split_combs = list(mask_plan.combiners)
    elif _config.get().oom_split_depth > 0:
        classified = _chunk_combiners(graph, fetch_list, summary)
        split_combs = (
            [classified[_base(f)] for f in fetch_list]
            if classified is not None
            else None
        )
    else:
        split_combs = None
    if mask_plan is not None:
        fn = _sp.masked_callable(ex, graph, fetch_list, feed_names, mask_plan)
    else:
        fn = ex.callable_for(graph, fetch_list, feed_names)
    # feed_src[j] = fetch whose partial re-feeds feed_names[j] (fetch
    # order and sorted-feed order differ with several fetches)
    fetch_of_feed = {_base(f) + "_input": i for i, f in enumerate(fetch_list)}
    feed_src = [fetch_of_feed[n] for n in feed_names]

    # Dispatch EVERY block before fetching anything: each fn call is an
    # async dispatch whose partial stays in device memory, so B blocks
    # queue back-to-back instead of serializing on a per-block
    # device->host copy (the per-task sync the reference paid in
    # `DataOps.scala:63-81`). maybe_check_numerics is a no-op unless the
    # debug mode is on, in which case it deliberately syncs per block to
    # name the offender.
    from .runtime import faults as _flt
    from .runtime import scheduler as _rs

    sched = _rs.schedule_for(frame, devices=devices, executor=ex)
    fscope = _flt.scope("reduce_blocks")
    fp = graph.fingerprint()
    partials: List[Tuple] = []
    owners: List[int] = []  # device slot per partial (scheduled runs)
    for bi in range(frame.num_blocks):
        lo, hi = frame.offsets[bi], frame.offsets[bi + 1]
        if lo == hi:
            # zero-row blocks (repartition(num_blocks > nrows)) are never
            # dispatched: a padded all-pad block would contribute the bare
            # reduction identity (e.g. +inf for Min) and poison the combine
            continue
        outs = _dispatch_reduce_block(
            "reduce_blocks.block", fp, fn, mask_plan, sched, fscope,
            bi, lo, hi,
            lambda lo_, hi_: [
                frame.column(mapping[n]).values[lo_:hi_]
                for n in feed_names
            ],
            split_combs, "reduce_blocks",
        )
        maybe_check_numerics(fetch_list, outs, f"reduce_blocks block {bi}")
        partials.append(tuple(outs))
        owners.append(sched.slot(bi) if sched is not None else 0)
    if not partials:
        raise ValueError("reduce_blocks on an empty frame")
    if len(partials) == 1:
        final = partials[0]
    else:
        def build_block_combine():
            import jax.numpy as jnp

            raw = build_callable(graph, fetch_list, feed_names)

            def combine(parts):
                stacked = [
                    jnp.stack([p[i] for p in parts]) for i in feed_src
                ]
                return raw(*stacked)

            return combine

        if sched is not None:
            final = _combine_partials_scheduled(
                ex, "reduce-combine", graph, fetch_list, feed_names,
                build_block_combine, partials, owners, sched,
                assoc=_assoc_reduce(graph, fetch_list, summary),
            )
        else:
            final = _combine_partials(
                ex, "reduce-combine", graph, fetch_list, feed_names,
                build_block_combine, partials,
            )
    if len(fetch_list) == 1:
        return final[0]
    return {_base(f): v for f, v in zip(fetch_list, final)}


# Streaming reduce lives in streaming.py; re-exported here so the
# public surface (and api._prefetch_iter-style internal references)
# are unchanged. Import is at the END of this module (late-bound).


# ---------------------------------------------------------------------------
# reduce_rows
# ---------------------------------------------------------------------------


def _validate_reduce_rows(summary: GraphSummary, fetch_list: List[str]) -> None:
    """`reduceRowsSchema` (`DebugRowOps.scala:172-262`): output ``x`` ↔
    placeholders ``x_1``/``x_2``, all three the same dtype and cell shape."""
    allowed = {_base(f) + s for f in fetch_list for s in ("_1", "_2")}
    extra = set(summary.inputs) - allowed
    if extra:
        raise ValueError(
            f"reduce_rows: placeholders {sorted(extra)} do not follow the "
            "x -> x_1/x_2 convention"
        )
    for f in fetch_list:
        base = _base(f)
        for suf in ("_1", "_2"):
            if base + suf not in summary.inputs:
                raise ValueError(
                    f"reduce_rows: output {base!r} requires placeholders "
                    f"{base}_1 and {base}_2 (inputs: {sorted(summary.inputs)})"
                )
        p1, p2 = summary.inputs[base + "_1"], summary.inputs[base + "_2"]
        out = summary.outputs[base]
        if not (p1.dtype is p2.dtype is out.dtype):
            raise ValueError(f"reduce_rows: dtype mismatch around {base!r}")
        if not (
            out.shape.check_more_precise_than(p1.shape)
            and out.shape.check_more_precise_than(p2.shape)
        ):
            raise ValueError(
                f"reduce_rows: shapes around {base!r} must all agree "
                f"(out {out.shape}, {base}_1 {p1.shape}, {base}_2 {p2.shape})"
            )


@_pandas_in_out
@_deadline_entry("reduce_rows")
def reduce_rows(
    fetches: Fetches,
    frame: TensorFrame,
    feed_dict: Optional[Dict[str, str]] = None,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
    mesh=None,
    devices=None,
):
    """Pairwise fold over all rows.

    `DebugRowOps.reduceRows` (`DebugRowOps.scala:486-508`): the reference
    folds each partition sequentially with one session.run PER ROW PAIR
    (`performReducePairwise`, `:939-979`). Here the pair graph is rolled
    into a `lax.scan` and the whole per-block fold is ONE XLA call; block
    partials then fold the same way. Fold order matches the reference
    (left fold in row order), so non-associative graphs agree too.

    On a `LazyFrame` this is a terminal action: the fused plan is
    forced first (one program per block), then the fold runs on the
    device-resident result.
    """
    from .lazy import LazyFrame

    if isinstance(frame, LazyFrame):
        frame = frame.force()
    from . import globalframe as _gf

    if isinstance(frame, _gf.GlobalFrame):
        # a left fold in row order is inherently sequential: cross the
        # local boundary (one block) and fold there — but the frame
        # still owns its placement, so per-call overrides stay loud
        _gf._reject_overrides("reduce_rows", mesh, devices)
        frame = frame.to_frame()
    if mesh is not None:
        from .parallel import verbs as _pverbs

        return _pverbs.reduce_rows(
            fetches, frame, mesh, feed_dict, fetch_names, executor
        )
    ex = executor or default_executor()
    graph, fetch_list = _as_graph(fetches, fetch_names)
    overrides = _ph_overrides(graph, frame, feed_dict, block_level=False)
    summary = analyze_graph(graph, fetch_list, placeholder_shapes=overrides)
    _validate_reduce_rows(summary, fetch_list)
    mapping = _match_columns(summary, frame, feed_dict, block_level=False)
    _require_dense(frame, list(mapping.values()), "reduce_rows")

    bases = [_base(f) for f in fetch_list]
    for b in bases:
        c1, c2 = mapping[b + "_1"], mapping[b + "_2"]
        if c1 != c2:
            raise ValueError(
                f"reduce_rows: {b}_1 reads column {c1!r} but {b}_2 reads "
                f"{c2!r}; a fold's carry and next-row must come from the "
                "same column"
            )
    feed_names = [b + s for b in bases for s in ("_1", "_2")]

    def fold_body():
        pair = build_callable(graph, fetch_list, feed_names)

        def fold(cols: Dict[str, "jax.Array"]):
            carry0 = tuple(cols[b][0] for b in bases)
            xs = tuple(cols[b][1:] for b in bases)

            def step(carry, xrow):
                feeds = []
                for i, _ in enumerate(bases):
                    feeds.extend((carry[i], xrow[i]))
                return tuple(pair(*feeds)), None

            carry, _ = lax.scan(step, carry0, xs)
            return carry

        return fold

    jfold = ex.cached(
        "fold", graph, fetch_list, feed_names, lambda: jax.jit(fold_body())
    )
    # async dispatch, device-resident partials: same discipline as
    # reduce_blocks — every block's fold is in flight before anything
    # is combined, and nothing is host-fetched on this path at all.
    # Scheduled runs spread the per-block folds across devices; the
    # FINAL combine always gathers every partial onto the anchor device
    # and folds them in block order (never hierarchically): the verb's
    # contract is a left fold in row order, which non-associative
    # graphs rely on — regrouping by device would break it.
    from .runtime import scheduler as _rs

    # single-row blocks never dispatch (their partial is a bare column
    # slice), so they carry zero planning weight — otherwise their slot's
    # queue-depth ledger would count a dispatch that never drains
    sched = _rs.schedule_weights(
        [0 if s == 1 else s for s in frame.block_sizes()],
        devices=devices, executor=ex,
    )
    from .runtime import faults as _flt

    # classified transient retry + failover only: the verb's contract is
    # a LEFT FOLD in row order, so a resource failure cannot split the
    # block (regrouping would change non-associative results) — OOM
    # surfaces exactly
    fscope = _flt.scope("reduce_rows")
    fp = graph.fingerprint()
    partials: List[Tuple] = []
    owners: List[int] = []
    for bi in range(frame.num_blocks):
        lo, hi = frame.offsets[bi], frame.offsets[bi + 1]
        if lo == hi:
            continue
        cols = {b: frame.column(mapping[b + "_1"]).values[lo:hi] for b in bases}
        if hi - lo == 1:
            partials.append(tuple(cols[b][0] for b in bases))
            owners.append(0)
        else:
            def _thunk(cols0=cols, bi=bi):
                # per-attempt span + per-attempt device_put: a failover
                # retry puts onto (and its span charges) the re-placed
                # device
                with _tele.dispatch_span(
                    "reduce_rows.block", program=fp, block=bi,
                    rows=hi - lo,
                    device=sched.label(bi) if sched is not None else None,
                ):
                    c = cols0
                    if sched is not None:
                        # dict feeds: device_put the values, keep keys
                        keys = list(c)
                        c = dict(
                            zip(keys, sched.put(bi, [c[k] for k in keys]))
                        )
                    return jfold(c)

            outs = fscope.dispatch(
                _thunk, what=f"reduce_rows block {bi}",
                sched=sched, index=bi,
            )
            maybe_check_numerics(bases, outs, f"reduce_rows block {bi}")
            partials.append(tuple(outs))
            owners.append(sched.slot(bi) if sched is not None else 0)
    if not partials:
        raise ValueError("reduce_rows on an empty frame")
    if len(partials) == 1:
        final = partials[0]
    else:
        def build_fold_combine():
            import jax.numpy as jnp

            fold = fold_body()

            def combine(parts):
                cols = {
                    b: jnp.stack([p[i] for p in parts])
                    for i, b in enumerate(bases)
                }
                return fold(cols)

            return combine

        if sched is not None:
            final = _combine_partials_scheduled(
                ex, "fold-combine", graph, fetch_list, feed_names,
                build_fold_combine, partials, owners, sched, assoc=False,
            )
        else:
            final = _combine_partials(
                ex, "fold-combine", graph, fetch_list, feed_names,
                build_fold_combine, partials,
            )
    if len(bases) == 1:
        return final[0]
    return dict(zip(bases, final))


# ---------------------------------------------------------------------------
# aggregate (keyed)
# ---------------------------------------------------------------------------


class GroupedFrame:
    """`frame.group_by(keys)` — the RelationalGroupedDataset analogue."""

    def __init__(self, frame: TensorFrame, keys: Sequence[str]):
        from .lazy import LazyFrame

        if isinstance(frame, LazyFrame):
            # aggregation is a terminal action for a lazy plan: the
            # fused chain lowers as one program per block here, then
            # the keyed plans see a concrete device-resident frame
            frame = frame.force()
        from . import globalframe as _gf

        self._from_global = isinstance(frame, _gf.GlobalFrame)
        if self._from_global:
            # keyed aggregation factorizes keys on the host: cross the
            # local boundary; the segment-plan aggregate then still
            # runs one transform dispatch over the single block. The
            # flag keeps `aggregate`'s placement-override rejection
            # loud even though the frame is local from here on.
            frame = frame.to_frame()
        self.frame = frame
        self.keys = list(keys)
        for k in self.keys:
            info = frame.info[k]
            if not info.cell_shape.is_scalar:
                raise ValueError(f"group key {k!r} must be a scalar column")
            # scalar columns are always groupable: dense ones directly,
            # string/object ones via Column.host_values() — the
            # reference grouped by ANY Catalyst column type, so string
            # keys (the common case from Arrow/Spark ingest) must work


def group_by(frame: TensorFrame, *keys: str) -> GroupedFrame:
    return GroupedFrame(frame, keys)


def _agg_spec_exprs(frame: TensorFrame, specs: Dict[str, Tuple[str, str]]):
    """Lower ``out=(op, column)`` aggregation specs to the DSL reduce
    fetches + feed_dict the `aggregate` verb wants — shared by the
    eager `GroupedFrame.agg` and the relational groupby plan node (both
    lower onto the same segment/vmap/chunk plans)."""
    from .graph.plan import AGG_OPS

    fetches = []
    feed: Dict[str, str] = {}
    for out, spec in sorted(specs.items()):
        if (
            not isinstance(spec, (tuple, list)) or len(spec) != 2
            or not all(isinstance(s, str) for s in spec)
        ):
            raise TypeError(
                f"agg spec {out}={spec!r}: want a ('op', 'column') pair"
            )
        op, colname = spec
        if op not in AGG_OPS:
            raise ValueError(f"agg op {op!r} is not one of {list(AGG_OPS)}")
        ph = dsl.block(frame, colname, tf_name=f"{out}_input")
        fetches.append(getattr(dsl, f"reduce_{op}")(ph, axes=[0]).named(out))
        feed[f"{out}_input"] = colname
    return fetches, feed


def scan(source, format: str = "auto", columns=None, chunk_groups: int = 1):
    """Lazily scan an on-disk dataset (parquet / arrow IPC) as a
    `RelationalFrame` — the relational plan's ingest leaf. Composes
    with `filter` / `select` / `map_blocks` / `group_by(...).agg(...)`;
    the plan optimizer pushes predicates and the pruned column set INTO
    the decode pipeline (skipping whole parquet row groups from footer
    stats), so a selective plan decodes the rows that survive, not the
    whole dataset. ``source`` is a path / path list / `ingest.Dataset`."""
    from .graph import plan as _plan
    from .ingest import Dataset
    from .lazy import RelationalFrame

    ds = (
        source
        if isinstance(source, Dataset)
        else Dataset(source, format=format, chunk_groups=chunk_groups)
    )
    payload: Dict[str, object] = {"dataset": ds}
    if columns is not None:
        payload["columns"] = tuple(columns)
    return RelationalFrame(_plan.PlanNode("scan", (), payload))


# The three aggregation plans live in aggregate.py (segment ops /
# exact per-size vmap / pow2-chunk monoid combine); re-exported below
# so parallel/verbs.py and parallel/multihost.py keep resolving them
# through this module.
from .aggregate import (  # noqa: E402
    _aggregate_chunked,
    _aggregate_segment,
    _chunk_combiners,
    _gid_dtype,
    _group_plan,
    _keyed_output,
    _monoid_combine,
)


@_deadline_entry("aggregate")
def aggregate(
    fetches: Fetches,
    grouped: GroupedFrame,
    feed_dict: Optional[Dict[str, str]] = None,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
    mesh=None,
    devices=None,
) -> TensorFrame:
    """Keyed aggregation with reduce_blocks naming conventions.

    `DebugRowOps.aggregate` (`DebugRowOps.scala:554-599`). The reference
    buffers up to 10 rows per group in a Catalyst UDAF and repeatedly
    compacts with a fresh TF session (`TensorFlowUDAF`, `:608-702`). Here
    rows are sorted by key once, and groups OF THE SAME SIZE are stacked
    and vmapped — one XLA call per distinct group size, each batched over
    all groups of that size.
    """
    if getattr(grouped, "_from_global", False):
        from . import globalframe as _gf

        _gf._reject_overrides("aggregate", mesh, devices)
    if mesh is not None:
        from .parallel import verbs as _pverbs

        return _pverbs.aggregate(
            fetches, grouped, mesh, feed_dict, fetch_names, executor
        )
    ex = executor or default_executor()
    frame = grouped.frame
    graph, fetch_list = _as_graph(fetches, fetch_names)
    overrides = _ph_overrides(graph, frame, feed_dict, block_level=True)
    summary = analyze_graph(graph, fetch_list, placeholder_shapes=overrides)
    _validate_reduce_blocks(summary, fetch_list)
    mapping = _match_columns(summary, frame, feed_dict, block_level=True)
    _require_dense(frame, list(mapping.values()), "aggregate")

    feed_names = sorted(summary.inputs)

    from . import config as _config

    # one structural classification serves the segment fast path AND the
    # chunked plan's eligibility check below
    classified = _chunk_combiners(graph, fetch_list, summary)
    from .utils.profiling import count as _count

    if (
        _config.get().aggregate_segment_fast
        and frame.nrows > 0
        and classified is not None
    ):
        # sort-free: one XLA call over all rows + device segment ops
        _count("aggregate.plan.segment")
        return _aggregate_segment(
            ex, graph, fetch_list, classified, feed_names, mapping, grouped,
            devices=devices,
        )

    key_out, num_groups, counts, starts, col_data = _group_plan(
        grouped, mapping, feed_names
    )
    vraw = ex.cached(
        "vmap-agg",
        graph,
        fetch_list,
        feed_names,
        lambda: jax.jit(
            jax.vmap(build_callable(graph, fetch_list, feed_names))
        ),
    )

    bases = [_base(f) for f in fetch_list]
    results: Dict[str, np.ndarray] = {}

    unique_sizes = np.unique(counts[counts > 0])
    combiners = None
    if len(unique_sizes) > _config.get().aggregate_exact_size_limit:
        # only chunk when the graph is provably chunk-safe; otherwise the
        # exact plan keeps correctness at the cost of more compiles
        combiners = classified
    _count(
        "aggregate.plan.exact" if combiners is None else "aggregate.plan.chunk"
    )

    fp = graph.fingerprint()
    if combiners is None:
        # exact plan: one vmapped call per distinct size, whole groups —
        # no associativity assumption, best for regular key distributions.
        # Two phases: dispatch EVERY per-size program first (partials
        # stay as device arrays; under the block scheduler the per-size
        # programs spread across local devices, weighted by their total
        # row count), then scatter into the host result — the first
        # host fetch happens only after all sizes are in flight, so
        # per-size device work overlaps instead of serializing on each
        # size's D2H copy.
        from .runtime import scheduler as _rs

        sched = _rs.schedule_weights(
            [int(s) * int((counts == s).sum()) for s in unique_sizes],
            devices=devices, executor=ex,
        )
        from .runtime import faults as _flt

        fscope = _flt.scope("aggregate")
        pending: List[Tuple[np.ndarray, Tuple]] = []
        with _tele.span("aggregate.plan.exact", kind="stage", program=fp):
            for si, size in enumerate(unique_sizes):
                gids = np.nonzero(counts == size)[0]
                row_idx = starts[gids][:, None] + np.arange(size)[None, :]
                feeds = [col_data[n][row_idx] for n in feed_names]  # (g, size, *cell)

                def _thunk(si=si, size=size, gids=gids, feeds=feeds):
                    # per-attempt span (see map_blocks._dispatch_rows)
                    call = (
                        sched.bind(si, vraw) if sched is not None else vraw
                    )
                    with _tele.dispatch_span(
                        "aggregate.size", program=fp,
                        rows=int(size) * len(gids), size=int(size),
                        device=sched.label(si)
                        if sched is not None
                        else None,
                    ):
                        return call(*feeds)

                outs = fscope.dispatch(
                    _thunk, what=f"aggregate groups of size {int(size)}",
                    sched=sched, index=si,
                )
                maybe_check_numerics(
                    bases, outs, f"aggregate groups of size {size}"
                )
                pending.append((gids, tuple(outs)))
        out_buffers: Dict[str, Optional[np.ndarray]] = {b: None for b in bases}
        for gids, outs in pending:
            for b, o in zip(bases, outs):
                o = np.asarray(o)
                if out_buffers[b] is None:
                    out_buffers[b] = np.zeros(
                        (num_groups,) + o.shape[1:], o.dtype
                    )
                out_buffers[b][gids] = o
        for b in bases:
            if out_buffers[b] is None:  # empty frame: zero groups
                out_buffers[b] = _empty_output(summary, b, drop_lead=False)
            results[b] = out_buffers[b]
    else:
        # pathological size distributions: pow2 chunk decomposition keeps
        # the compile count O(log max_size) instead of O(#distinct sizes)
        with _tele.span("aggregate.plan.chunk", kind="stage", program=fp):
            results.update(
                _aggregate_chunked(
                    lambda feeds: vraw(*feeds),
                    feed_names,
                    col_data,
                    counts,
                    starts,
                    num_groups,
                    bases,
                    combiners,
                    program=fp,
                    executor=ex,
                    devices=devices,
                )
            )

    return _keyed_output(key_out, results, bases)


# ---------------------------------------------------------------------------
# schema utilities
# ---------------------------------------------------------------------------


def analyze(frame: TensorFrame) -> TensorFrame:
    """Scan the data and refine column shapes (`ExperimentalOperations.analyze`)."""
    return frame.analyze()


def print_schema(frame: TensorFrame) -> None:
    """`tfs.print_schema` (`core.py:355-364`)."""
    frame.print_schema()


def append_shape(frame: TensorFrame, col: str, shape) -> TensorFrame:
    """`tfs.append_shape` (`ExperimentalOperations.scala:53-68`)."""
    if not isinstance(shape, Shape):
        shape = Shape(shape)
    return frame.append_shape(col, shape)


def explain(frame: TensorFrame) -> str:
    """`OperationsInterface.explain` (`DebugRowOps.scala:535-552`).

    For a `LazyFrame`, renders the fused plan with per-stage provenance
    (deferred verbs, feeds, pending outputs) above the schema. For a
    `RelationalFrame` (or its `LazyPlan`), renders the pre- AND
    post-optimization DAG with per-node costed estimates and every
    rewrite decision (accepted and rejected) — WITHOUT executing."""
    from .lazy import LazyFrame, LazyPlan, RelationalFrame

    if isinstance(frame, RelationalFrame):
        return frame.explain_plan()
    if isinstance(frame, LazyPlan):
        if frame.relational is not None:
            return RelationalFrame(frame.relational).explain_plan()
        return repr(frame)
    if isinstance(frame, LazyFrame):
        return frame.explain_plan()
    return frame.info.explain()


def explain_detailed(frame: TensorFrame):
    """Structured per-column tensor metadata, the analogue of
    `ExperimentalOperations.explainDetailed` (`ExperimentalOperations.scala:27`):
    returns the `FrameInfo` itself rather than a rendered string. For a
    `LazyFrame`, returns the structured `LazyPlan` (stages, fused graph,
    column sources, feeds, virtual schema)."""
    from .lazy import LazyFrame

    if isinstance(frame, LazyFrame):
        return frame.plan()
    return frame.info


# inspection helpers live in utils/inspection.py (re-exported below)


def block_to_row(frame: TensorFrame) -> TensorFrame:
    """Convert each block to a single row, augmenting every column's rank
    by one (lead dim = block row count).

    The reference declares this operation but never implements it
    (`ExperimentalOperations.convertBlockToRow` is literally `???`,
    `ExperimentalOperations.scala:25`); here it is real. Blocks of unequal
    size produce a ragged column (lead dim Unknown), exactly like the
    reference's variable-length rows."""
    per_col_cells: Dict[str, list] = {name: [] for name in frame.columns}
    for blk in frame.blocks():
        for name in frame.columns:
            col = blk[name]
            if col.is_dense:
                per_col_cells[name].append(np.asarray(col.values))
            else:
                # ragged rows inside a block cannot stack into one cell
                raise ValueError(
                    f"block_to_row: column {name!r} is ragged; analyze/pad first"
                )
    cols = [
        Column(name, per_col_cells[name], frame[name].dtype)
        for name in frame.columns
    ]
    return TensorFrame(cols)


def block(frame: TensorFrame, col_name: str, tf_name: Optional[str] = None):
    """Block placeholder for a column (`core.py:451-474`, `tfs.block`).

    Accepts a pandas DataFrame too (the reference's local-debug path,
    `core.py:263-265`, takes pandas through the same ``tfs.*`` calls)."""
    if _is_pandas(frame):
        frame = TensorFrame.from_pandas(frame)
    return dsl.block(frame, col_name, tf_name)


def row(frame: TensorFrame, col_name: str, tf_name: Optional[str] = None):
    """Row placeholder for a column (`tfs.row`)."""
    if _is_pandas(frame):
        frame = TensorFrame.from_pandas(frame)
    return dsl.row(frame, col_name, tf_name)


# ---------------------------------------------------------------------------
# fluent methods (the reference's Scala Implicits: RichDataFrame adds
# df.mapBlocks(...)/df.mapRows/... and RichRelationalGroupedDataset adds
# .aggregate — `dsl/Implicits.scala:25-124`)
# ---------------------------------------------------------------------------


def _install_fluent_methods() -> None:
    def _map_blocks(self, fetches, **kw):
        return map_blocks(fetches, self, **kw)

    def _map_rows(self, fetches, **kw):
        return map_rows(fetches, self, **kw)

    def _reduce_blocks(self, fetches, **kw):
        return reduce_blocks(fetches, self, **kw)

    def _reduce_rows(self, fetches, **kw):
        return reduce_rows(fetches, self, **kw)

    def _group_by(self, *keys):
        return GroupedFrame(self, keys)

    _slice_block = TensorFrame.block

    def _block(self, arg, tf_name=None):
        # polymorphic like the reference's dual use: df.block(i) slices
        # block i; df.block("col") builds a placeholder for the column
        if isinstance(arg, str):
            return dsl.block(self, arg, tf_name)
        return _slice_block(self, arg)

    def _row(self, col, tf_name=None):
        return dsl.row(self, col, tf_name)

    # relational verbs: compose lazily as plan-DAG nodes (graph.plan);
    # force() runs them through the cost-based optimizer
    def _filter(self, pred, selectivity=None):
        return self.lazy().filter(pred, selectivity=selectivity)

    def _sort_by(self, *keys, descending=False):
        return self.lazy().sort_by(*keys, descending=descending)

    def _join(self, other, on, how="inner"):
        return self.lazy().join(other, on, how=how)

    TensorFrame.map_blocks = _map_blocks
    TensorFrame.map_rows = _map_rows
    TensorFrame.reduce_blocks = _reduce_blocks
    TensorFrame.reduce_rows = _reduce_rows
    TensorFrame.group_by = _group_by
    TensorFrame.block = _block
    TensorFrame.row = _row
    TensorFrame.filter = _filter
    TensorFrame.sort_by = _sort_by
    TensorFrame.join = _join

    def _agg(self, fetches, **kw):
        return aggregate(fetches, self, **kw)

    def _agg_specs(self, **specs):
        """Keyed aggregation from ``out=('op', column)`` specs (ops:
        sum / mean / min / max) — the eager sibling of the relational
        `LazyGroupedFrame.agg`; lowers onto the same segment/vmap
        aggregation plans."""
        fetches, feed = _agg_spec_exprs(self.frame, specs)
        return aggregate(fetches, self, feed_dict=feed)

    GroupedFrame.aggregate = _agg
    GroupedFrame.agg = _agg_specs


_install_fluent_methods()


# late import: streaming.py references this module's helpers at call
# time, so it must load after every definition above
from .fn_frontend import (  # noqa: E402
    _assemble_ragged,
    _empty_fn_outputs,
    _fn_feed_columns,
    _fn_outputs_to_dict,
    _map_blocks_fn,
    _map_rows_fn,
    _run_ragged_bucketed,
)
from .lazy import LazyFrame, lazy  # noqa: E402
from .streaming import _prefetch_iter, reduce_blocks_stream  # noqa: E402
from .utils.inspection import (  # noqa: E402
    _lower_for_inspection,
    cost_analysis,
    executor_stats,
    explain_hlo,
)
