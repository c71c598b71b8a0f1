"""Plain-function front-end kernels + ragged bucketed execution.

The TPU-native tracer front-end: verbs accept a plain Python function
over column arrays (no GraphDef needed). `_map_blocks_fn` /
`_map_rows_fn` plan such a call (`_plan_fn`: the function's program from
the executor's cache, bound pytrees placed) and hand it to the block
loop the graph front end runs, `api._run_blocks`; `_run_ragged_bucketed`
is the shape-bucketing plan shared by the graph and function per-row
paths (and, per shard, by `parallel.verbs._ragged_per_shard`).
Extracted from `api.py` (round-4 verdict task 7); `api.py` re-exports
every name, so `api._run_ragged_bucketed`-style references and the
public behavior are unchanged.
"""

from __future__ import annotations

import inspect
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from .frame import Column, TensorFrame

from .runtime.executor import Executor, FnProgram

# late-bound: api imports this module at its end; helper lookups
# resolve at call time through the module object
from . import api as _api


def _empty_fn_outputs(jfn, feeds: List) -> Dict[str, np.ndarray]:
    """Zero-row outputs for a function-front-end verb over an all-empty
    frame: trace the jitted fn on zero-row feeds (shape-level only). The
    lead dim is forced to 0 — a trimmed reduction traced on a zero-row
    block can still report a nonzero lead (e.g. keepdims sums)."""
    shapes = jax.eval_shape(jfn, *feeds)
    return {
        n: np.zeros((0,) + s.shape[1:], s.dtype) for n, s in shapes.items()
    }


def _empty_of(jfn, params, columns, bound) -> Dict[str, np.ndarray]:
    """`_empty_fn_outputs` of a planned verb: zero rows of every column
    feed, the bound values whole."""
    return _empty_fn_outputs(
        jfn, [bound[p].on() if p in bound else columns[p][:0] for p in params]
    )


def _fn_feed_columns(
    fn: Callable, frame: TensorFrame, bound: Optional[set] = None
) -> List[str]:
    params = [
        p.name
        for p in inspect.signature(fn).parameters.values()
        if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)
    ]
    missing = [
        p for p in params if p not in frame.info and p not in (bound or ())
    ]
    if missing:
        raise ValueError(
            f"function front-end: parameters {missing} have no matching "
            f"columns (columns: {frame.columns})"
        )
    return params


def _fn_outputs_to_dict(res, what: str) -> Dict[str, "jax.Array"]:
    if isinstance(res, dict):
        if not res:
            # an empty dict would sail through the per-block loops and
            # only explode later (e.g. the mesh trim path's np.cumsum
            # over a None block size); fail at the verb with the cause
            raise ValueError(
                f"{what}: the function graph returned an empty dict; it "
                "must return at least one named output array (output "
                "names become column names)"
            )
        return res
    raise ValueError(
        f"{what}: a function graph must return a dict of named output "
        "arrays (output names become column names)"
    )


def _plan_fn(
    verb: str, fn: Callable, frame: TensorFrame, ex: Executor, bindings,
    devices, kind: str, wrap: Callable[[Callable, List[str]], Callable],
):
    """The plan of a function-front-end verb, under the same spans as a
    graph's: parameters matched to columns and bound values, the
    function's program out of the executor's cache (keyed by the
    function, the bound trees' structures and the parameter names, so a
    second call of the same function traces and compiles nothing), the
    schedule, and the bound values placed on its devices."""
    from .runtime import bindings as _rb
    from .runtime import scheduler as _sched

    bindings = dict(bindings or {})
    with _api._tele.span(f"{verb}.plan", kind="stage"):
        with _api._tele.span("frame.match"):
            params = _fn_feed_columns(fn, frame, bound=set(bindings))
            unknown = sorted(set(bindings) - set(params))
            if unknown:
                raise ValueError(
                    f"bindings {unknown} do not match any function parameter "
                    f"(parameters: {params})"
                )
        program = FnProgram(fn)

        def run(*args):
            return _fn_outputs_to_dict(fn(*args), verb)

        # the XLA module is named after the function (`jit_<name>`), so a
        # device trace tells one function's program from another's
        run.__name__ = run.__qualname__ = getattr(fn, "__name__", "fn")
        with _api._tele.span("executor.lookup"):
            jfn = ex.cached(
                kind, program, (), list(params) + list(_rb.structure(bindings)),
                # a `jax.jit` as a graph's program is: the native executor
                # takes it as a lowering recipe and runs it on its own host
                lambda: jax.jit(wrap(run, params)),
            )
        with _api._tele.span("scheduler.plan"):
            sched = _sched.schedule_for(frame, devices=devices, executor=ex)
        bound = _api._place_bindings(bindings, sched, ex)
    return params, program.fingerprint(), jfn, sched, bound


def _map_blocks_fn(
    fn: Callable,
    frame: TensorFrame,
    trim: bool,
    ex: Executor,
    bindings: Optional[Dict[str, object]] = None,
    devices=None,
) -> TensorFrame:
    """Function front end of map_blocks: ``fn(column block, ...,
    bound value, ...) -> dict of outputs``, on `api._run_blocks`. A
    function cannot be shown row-local, so its blocks are dispatched at
    their exact shapes."""
    params, fp, jfn, sched, bound = _plan_fn(
        "map_blocks", fn, frame, ex, bindings, devices, "fn-block",
        lambda f, params: f,
    )
    cols = [p for p in params if p not in bound]
    _api._require_dense(frame, cols, "map_blocks")
    columns = {p: frame.column(p).values for p in cols}
    out_cols, offsets = _api._run_blocks(
        "map_blocks", frame, jfn, fp, params, columns, bound, None, sched,
        trim=trim, empty=lambda: _empty_of(jfn, params, columns, bound),
    )
    return _api._output_frame(
        frame, out_cols, append_input=not trim, offsets=offsets
    )


def _run_ragged_bucketed(
    vfn,
    columns: List[Column],
    nrows: int,
    out_names_hint: Optional[List[str]] = None,
    defer: bool = False,
) -> Dict[str, List[np.ndarray]]:
    """Shape-bucketed execution for ragged rows: group rows by their joint
    cell-shape signature, run ONE vmapped XLA call per bucket, scatter the
    results back in row order.

    This is the shape-bucketing plan of SURVEY §7 "hard parts" — the ragged
    analogue of the reference's per-row variable-length support
    (`TFDataOps.scala:90-103`) without its one-session.run-per-row cost.
    Bucket sizes are padded to the next power of two (duplicating the last
    row; padded outputs discarded) so the compile count is bounded by
    O(#distinct cell shapes x log max bucket) instead of O(#rows).

    ``vfn`` is a vmapped callable returning either a tuple (graph path,
    ``out_names_hint`` gives the names) or a dict (function front-end).
    Returns name -> list of per-row output cells (row order).

    ``defer=True`` returns the raw chunk pairs (name -> [(row indices,
    DEVICE array)]) without assembling: the mesh ragged path
    (`parallel.verbs._ragged_per_shard`) runs this once per device and
    must not block on device-to-host transfer between shards — it
    collects every shard's chunks and assembles once at the end via
    `_assemble_ragged`.
    """
    cells = [c.values if c.is_dense else c.ragged for c in columns]
    buckets: Dict[Tuple, List[int]] = {}
    for i in range(nrows):
        key = tuple(cc[i].shape for cc in cells)
        buckets.setdefault(key, []).append(i)

    # (idxs, chunk) pairs per output name; assembled dense below when all
    # buckets agree on the output cell shape, else per-row (ragged result)
    chunks: Dict[str, List[Tuple[np.ndarray, np.ndarray]]] = {}
    for idxs in buckets.values():
        nb = len(idxs)
        padded = 1 << (nb - 1).bit_length()
        take = idxs + [idxs[-1]] * (padded - nb)
        feeds = [
            cc[np.asarray(take)]
            if col.is_dense
            else np.stack([cc[i] for i in take])
            for col, cc in zip(columns, cells)
        ]
        outs = vfn(*feeds)
        if not isinstance(outs, dict):
            outs = dict(zip(out_names_hint, outs))
        idx_arr = np.asarray(idxs)
        for name, o in outs.items():
            # keep the DEVICE array (slicing is lazy): converting here
            # would block on transfer before the next bucket dispatches,
            # serializing the whole plan — with per-shard device
            # placement (parallel.verbs._ragged_per_shard) every
            # device's buckets must be in flight before any fetch
            chunks.setdefault(name, []).append((idx_arr, o[:nb]))

    if defer:
        return chunks
    return _assemble_ragged(chunks, nrows)


def _assemble_ragged(
    chunks: Dict[str, List[Tuple[np.ndarray, "jax.Array"]]], nrows: int
) -> Dict[str, Union[np.ndarray, List[np.ndarray]]]:
    """Scatter bucketed chunk outputs back into row order. Device->host
    conversion happens HERE, after every bucket (and, for the mesh path,
    every shard's device) has been dispatched."""
    per_row: Dict[str, Union[np.ndarray, List[np.ndarray]]] = {}
    for name, pairs in chunks.items():
        cell_shapes = {o.shape[1:] for _, o in pairs}
        if len(cell_shapes) == 1:  # uniform outputs: one dense scatter
            shape = next(iter(cell_shapes))
            res = np.empty((nrows,) + shape, dtype=pairs[0][1].dtype)
            for idx_arr, o in pairs:
                res[idx_arr] = np.asarray(o)
            per_row[name] = res
        else:
            rows: List[Optional[np.ndarray]] = [None] * nrows
            for idx_arr, o in pairs:
                o = np.asarray(o)
                for j, i in enumerate(idx_arr):
                    rows[i] = o[j]
            per_row[name] = rows
    return per_row


def _map_rows_fn(
    fn: Callable,
    frame: TensorFrame,
    ex: "Executor",
    bindings: Optional[Dict[str, object]] = None,
    devices=None,
) -> TensorFrame:
    """Function front-end for map_rows: fn(cell, ...) -> dict of outputs.

    jit/vmap preserve dict outputs, so output names come from the traced
    dict directly — the user function is invoked exactly once per trace.
    ``bindings`` match function PARAMETER names and are held constant
    across rows (vmap in_axes=None), like the graph front-end. Dense
    columns run on `api._run_blocks`: the vmapped program is
    row-independent by construction, so its blocks take the shape policy
    and the OOM split; ragged columns go to `_run_ragged_bucketed`.
    """
    bindings = dict(bindings or {})
    col_params = [
        p for p in _fn_feed_columns(fn, frame, bound=set(bindings))
        if p not in bindings
    ]
    if bindings and not col_params:
        raise ValueError(
            "map_rows: every parameter is bound, so nothing varies per "
            "row; use map_blocks (or call the function directly)"
        )
    dense = all(frame.column(p).is_dense for p in col_params)
    if bindings and not dense:
        raise ValueError(
            "map_rows: bindings are not supported with ragged feed "
            "columns; densify the columns or bake the values as constants"
        )
    params, fp, vfn, sched, bound = _plan_fn(
        "map_rows", fn, frame, ex, bindings, devices if dense else None,
        "fn-vmap-rows",  # which parameters are bound is in the cache key
        lambda f, params: jax.vmap(
            f, in_axes=tuple(None if p in bindings else 0 for p in params)
        ),
    )
    if dense:
        from . import shape_policy as _sp

        columns = {p: frame.column(p).values for p in col_params}
        out_cols, _ = _api._run_blocks(
            "map_rows", frame, vfn, fp, params, columns, bound, None, sched,
            rowwise=True, bucketed=_sp.enabled(ex),
            empty=lambda: _empty_of(vfn, params, columns, bound),
        )
    elif frame.nrows == 0:
        # 0-row ragged columns: synthesize zero-row feeds from the
        # declared cell shapes (unknown dims collapse to 0)
        feeds = [
            np.zeros(
                (0,)
                + tuple(
                    0 if d is None else d
                    for d in frame.column(p).cell_shape.dims
                ),
                dtype=frame.column(p).dtype.np_dtype,
            )
            for p in params
        ]
        out_cols = [
            Column(n, v) for n, v in _empty_fn_outputs(vfn, feeds).items()
        ]
    else:
        per_out = _run_ragged_bucketed(
            vfn, [frame.column(p) for p in params], frame.nrows
        )
        out_cols = [Column(n, vals) for n, vals in per_out.items()]
    return _api._output_frame(frame, out_cols, append_input=True)
