"""Distributed verbs: the five operations over a device mesh.

Execution topology vs the reference (SURVEY.md §2.5, §5):

- ``map_blocks``: one block per device via `shard_map` over the ``data``
  axis — each shard applies the graph independently, exactly the
  "every partition runs the same frozen graph" model
  (`DebugRowOps.scala:384-398`) with devices in place of executors.
- ``reduce_blocks`` / ``reduce_rows``: per-shard reduce, then
  `lax.all_gather` of the per-shard partials over ICI and a final
  application of the same graph to the gathered stack — all inside ONE
  jitted program. This replaces the driver-funneled pairwise
  `RDD.reduce` (`DebugRowOps.scala:507,530-533`): no host round-trip, no
  pairwise session churn, and XLA is free to turn gather+reduce into an
  all-reduce tree over ICI.
- ``aggregate``: per-shard segment-sum into a dense (num_keys, ...) table,
  then `psum` across shards — the UDAF + Catalyst-shuffle topology
  (`DebugRowOps.scala:608-702`) becomes two collectives.

Rows are split into `ndev` equal shards; a remainder tail (rows % ndev)
runs as one extra block on a single device and its partial joins the
combine — block boundaries are arbitrary in the reference too (Spark
chose partition sizes), so this changes nothing semantically.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from functools import lru_cache, partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..frame import Column, TensorFrame
from ..graph import builder as dsl
from ..graph.analysis import analyze_graph
from ..graph.ir import Graph, base_name, parse_edge
from ..ops.lowering import build_callable
from .. import api as _api
from ..runtime.executor import Executor, default_executor, lru_get_or_insert
from ..runtime.faults import maybe_check_numerics

__all__ = [
    "map_blocks",
    "map_rows",
    "reduce_blocks",
    "reduce_rows",
    "aggregate",
    "fused_map_blocks",
    "fused_reduce_blocks",
]


_base = base_name


import contextlib


@contextlib.contextmanager
def _mesh_dispatch(name: str, program, rows: int, shards: int):
    """THE mesh-dispatch instrumentation wrapper: a `record()` span
    (``name.calls``/``.seconds``/``.rows`` counters + a ``verb`` span)
    with a nested ``dispatch`` leaf labeled by program fingerprint and
    shard count — mesh dispatches previously bypassed profiling
    entirely (only the api-level verb recorded)."""
    from ..utils import telemetry as _tele
    from ..utils.profiling import record as _rec

    with _rec(name, rows):
        with _tele.dispatch_span(
            name, program=program, rows=rows, shards=shards
        ):
            yield


def _mesh_call(name: str, program, rows: int, shards: int, fn, *args):
    """`_mesh_dispatch` instrumentation + classified transient retries
    (`runtime.faults`): one shard_map program is the mesh path's unit
    of re-execution — a pure function of its feeds, exactly like a
    block dispatch. Deterministic errors surface after one attempt;
    there is no device failover inside a mesh (the mesh OWNS its
    placement — losing a mesh device fails the verb) and no OOM split
    (halving rows would change the shard layout), so resource errors
    surface exactly."""
    from .. import config as _config
    from ..runtime import faults as _faults

    with _mesh_dispatch(name, program, rows, shards):
        return _faults.run_with_retries(
            fn, *args,
            attempts=_config.get().block_retry_attempts,
            what=name, verb=name,
        )


@lru_cache(maxsize=64)
def _mesh_sig(mesh: Mesh) -> str:
    """Cache-key signature of a mesh's concrete device identity. A
    cached shard_map program is bound to the devices it was traced
    over; two meshes with the same device COUNT but different devices
    (or a different topology) must never share an executor-cache entry,
    or the reused program would run on the old mesh's chips.

    Memoized per Mesh (hashable in jax) — on a pod-scale mesh the
    O(ndev) string build would otherwise run on every verb dispatch,
    the same hot-path cost `Graph.fingerprint` memoizes away."""
    shape = "x".join(str(int(n)) for n in mesh.devices.shape)
    # device ids are unique only per backend: cpu:0 and tpu:0 are both
    # id 0, so the platform must disambiguate (virtual-CPU dry run
    # followed by a real TPU run in one process must not share entries)
    ids = ",".join(
        f"{getattr(d, 'platform', '?')}:{int(d.id)}" for d in mesh.devices.flat
    )
    return f"{shape}@{ids}"


def _split(frame: TensorFrame, cols: Sequence[str], ndev: int):
    """(main arrays with lead = s*ndev, tail arrays with lead = r)."""
    n = frame.nrows
    s = n // ndev
    main = {c: frame.column(c).values[: s * ndev] for c in cols}
    tail = {c: frame.column(c).values[s * ndev :] for c in cols}
    return main, tail, s


def _bucketed_or_split(ex, frame, cols_used, ndev, graph, fetches, ph_ranks):
    """THE map-verb mesh bucketing gate (`map_blocks` and
    `fused_map_blocks` share it): when the shape policy is on and the
    graph is row-local, pad the whole frame so every shard is one
    bucket-ladder rung — one static `shard_map` shape per rung, no
    varying-remainder tail program; pad rows replicate the last row and
    are sliced off by the caller. Otherwise the ordinary `_split`.
    Returns ``(main, tail, s, pad_rows)`` with ``pad_rows == 0`` on the
    unbucketed path."""
    from .. import shape_policy as _sp

    if (
        cols_used
        and frame.nrows > 0
        and _sp.enabled(ex)
        and _sp.rowwise_fetches(graph, fetches, ph_ranks)
    ):
        main, tail, s, _ = _sp.pad_mesh_shards(frame, cols_used, ndev)
        return main, tail, s, s * ndev - frame.nrows
    main, tail, s = _split(frame, cols_used, ndev)
    return main, tail, s, 0


def _mesh_in_specs(params, bindings, main, col_of=None):
    """shard_map in_specs shared by every mesh map verb: bound args are
    replicated (P(None...)), column feeds shard their lead dim over the
    ``data`` axis. ``col_of`` maps a placeholder/param name to its frame
    column (identity for the function front-end)."""
    col_of = col_of or (lambda p: p)
    return tuple(
        P(*([None] * bindings[p].ndim))
        if p in bindings
        else P("data", *([None] * (main[col_of(p)].ndim - 1)))
        for p in params
    )


def _on_mesh(mesh: Mesh, in_specs, feeds) -> List:
    """Commit device-resident feeds to ``mesh`` under their shard_map
    specs. A verb output (or ``df.to_device()`` column) is committed to
    the device that produced it, and jit refuses committed arguments
    whose devices differ from the shard_map's ("incompatible devices");
    host arrays pass through — jit shards those itself."""
    return [
        jax.device_put(f, NamedSharding(mesh, sp))
        if isinstance(f, jax.Array)
        else f
        for f, sp in zip(feeds, in_specs)
    ]


def _concat_parts(mesh: Mesh, parts: List):
    """`api._concat_parts` for mesh verbs: the shard output lives on the
    mesh, while a tail computed from a device-committed frame lives on
    that one device — replicate such a part over the mesh first, so the
    concatenate sees one device set."""
    if len(parts) > 1 and mesh.devices.size > 1:
        rep = NamedSharding(mesh, P())
        parts = [
            jax.device_put(p, rep)
            if isinstance(p, jax.Array)
            and p.committed
            and len(p.sharding.device_set) == 1
            else p
            for p in parts
        ]
    return _api._concat_parts(parts)


# ---------------------------------------------------------------------------
# map_blocks
# ---------------------------------------------------------------------------


def map_blocks(
    fetches,
    frame: TensorFrame,
    mesh: Mesh,
    feed_dict: Optional[Dict[str, str]] = None,
    trim: bool = False,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
    bindings: Optional[Dict[str, "np.ndarray"]] = None,
) -> TensorFrame:
    """Distributed map_blocks: one block per device.

    Trimmed maps work too: the same graph on same-shaped shards produces
    the same output row count on every device (XLA static shapes), so the
    shard outputs concatenate cleanly — each device's rows form one block.
    Bound placeholders (``bindings``) are replicated to every device.
    """
    ex = executor or default_executor()
    bindings = {k: np.asarray(v) for k, v in (bindings or {}).items()}
    if callable(fetches) and not isinstance(fetches, dsl.Tensor):
        return _fn_mesh(
            fetches, frame, mesh, trim=trim, bindings=bindings, per_row=False
        )
    graph, fetch_list = _api._as_graph(fetches, fetch_names)
    overrides = _api._ph_overrides(
        graph, frame, feed_dict, block_level=True, bindings=bindings
    )
    summary = analyze_graph(graph, fetch_list, placeholder_shapes=overrides)
    _api._check_bindings(summary, bindings)
    mapping = _api._match_columns(
        summary, frame, feed_dict, block_level=True, bindings=bindings
    )
    _api._require_dense(frame, list(mapping.values()), "map_blocks")

    feed_names = sorted(summary.inputs)
    col_feeds = [n for n in feed_names if n not in bindings]
    cols_used = [mapping[n] for n in col_feeds]
    ndev = mesh.devices.size
    if trim or bindings:  # trim changes row counts; bindings replicate
        main, tail, s = _split(frame, cols_used, ndev)
        pad_rows = 0
    else:
        main, tail, s, pad_rows = _bucketed_or_split(
            ex, frame, cols_used, ndev, graph, fetch_list,
            {p: ph.shape.rank for p, ph in summary.inputs.items()},
        )

    fn = build_callable(graph, fetch_list, feed_names)
    acc: Dict[str, List] = {_base(f): [] for f in fetch_list}
    block_sizes: List[int] = []

    def _feeds(source: Dict[str, "np.ndarray"]) -> List:
        return [
            bindings[n] if n in bindings else source[mapping[n]]
            for n in feed_names
        ]

    if s > 0:
        in_specs = _mesh_in_specs(
            feed_names, bindings, main, col_of=mapping.__getitem__
        )
        out_specs = P("data")
        # in_specs depend on WHICH placeholders are bound (replicated) and
        # on feed ranks — both must be part of the cache key, or a later
        # call with a different binding set would reuse a shard_map whose
        # specs shard/replicate the wrong arguments.
        spec_sig = ";".join(str(s) for s in in_specs)
        sharded = ex.cached(
            f"shmap-{_mesh_sig(mesh)}-[{spec_sig}]",
            graph,
            fetch_list,
            feed_names,
            lambda: jax.jit(
                shard_map(
                    fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs
                )
            ),
        )
        outs = _mesh_call(
            "mesh.map_blocks", graph.fingerprint(), s * ndev, ndev,
            sharded, *_on_mesh(mesh, in_specs, _feeds(main)),
        )
        maybe_check_numerics(fetch_list, outs, "map_blocks (mesh shards)")
        shard_out = None
        for f, o in zip(fetch_list, outs):
            if not trim and o.shape[0] != s * ndev:
                raise ValueError(
                    f"map_blocks: output {f!r} does not preserve the block "
                    "row count; use trim=True for row-count-changing maps"
                )
            if trim:
                if shard_out is None:
                    shard_out = o.shape[0] // ndev
                elif o.shape[0] // ndev != shard_out:
                    raise ValueError(
                        "map_blocks(trim): outputs disagree on row count"
                    )
            acc[_base(f)].append(o[: frame.nrows] if pad_rows else o)
        block_sizes += [shard_out if trim else s] * ndev
    if cols_used and tail[cols_used[0]].shape[0] > 0:
        tfn = ex.callable_for(graph, fetch_list, feed_names)
        outs = _mesh_call(
            "mesh.map_blocks.tail", graph.fingerprint(),
            tail[cols_used[0]].shape[0], 1, tfn, *_feeds(tail),
        )
        maybe_check_numerics(fetch_list, outs, "map_blocks (mesh tail)")
        tail_out = None
        for f, o in zip(fetch_list, outs):
            if trim:
                tail_out = o.shape[0]
            acc[_base(f)].append(o)
        block_sizes.append(
            tail_out if trim else tail[cols_used[0]].shape[0]
        )

    out_cols = [
        Column(
            _base(f),
            _concat_parts(mesh, acc[_base(f)])
            if acc[_base(f)]
            else _api._empty_output(summary, _base(f), drop_lead=True),
        )
        for f in fetch_list
    ]
    if trim:
        offsets = list(np.cumsum([0] + (block_sizes or [0])))
        return _api._output_frame(
            frame, out_cols, append_input=False, offsets=offsets
        )
    return _api._output_frame(
        frame, out_cols, append_input=True, offsets=frame.offsets
    )


# ---------------------------------------------------------------------------
# map_rows
# ---------------------------------------------------------------------------


def _ragged_per_shard(
    vfn,
    columns: Sequence[Column],
    nrows: int,
    mesh: Mesh,
    out_names_hint: Optional[List[str]] = None,
):
    """The ragged bucket plan applied PER SHARD, one shard per device.

    Rows split into ``ndev`` contiguous shards; each shard runs the
    bucketed vmap (`api._run_ragged_bucketed`) with its feeds committed
    to that shard's device, so XLA executes shard ``d``'s buckets on
    device ``d`` — the reference's every-executor-runs-its-partition
    model (`DebugRowOps.scala:403-484`) with devices for executors.
    shard_map itself cannot carry ragged cells (XLA static shapes), so
    the spread is by input placement: dispatch is async, and the Python
    loop issues work to all devices before blocking on results.
    """
    devices = list(mesh.devices.flat)
    bounds = np.linspace(0, nrows, len(devices) + 1).astype(int)
    # deferred chunks from EVERY shard are collected before any
    # device->host fetch: the Python loop issues all shards' buckets
    # (async dispatch onto their devices) and _assemble_ragged blocks
    # only once, at the end
    all_chunks: Dict[str, List] = {}
    for d, dev in enumerate(devices):
        lo, hi = int(bounds[d]), int(bounds[d + 1])
        if lo == hi:
            continue

        def dev_vfn(*feeds, _dev=dev):
            return vfn(*[jax.device_put(f, _dev) for f in feeds])

        shard_cols = [
            Column(
                c.name,
                c.values[lo:hi] if c.is_dense else list(c.ragged[lo:hi]),
                c.dtype,
            )
            for c in columns
        ]
        chunks = _api._run_ragged_bucketed(
            dev_vfn, shard_cols, hi - lo,
            out_names_hint=out_names_hint, defer=True,
        )
        for name, pairs in chunks.items():
            all_chunks.setdefault(name, []).extend(
                (idx + lo, o) for idx, o in pairs
            )
    return _api._assemble_ragged(all_chunks, nrows)


def map_rows(
    fetches,
    frame: TensorFrame,
    mesh: Mesh,
    feed_dict: Optional[Dict[str, str]] = None,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
    bindings: Optional[Dict[str, "np.ndarray"]] = None,
) -> TensorFrame:
    """Distributed map_rows: rows shard across the mesh ``data`` axis.

    `DebugRowOps.mapRows` ran over every Spark partition like the other
    verbs (`DebugRowOps.scala:403-484`); here dense columns run as ONE
    ``shard_map(vmap(graph))`` program — per-row vectorization inside
    each shard, shards across devices — with the remainder tail
    (rows % ndev) vmapped on one device exactly like the local verb.
    Ragged columns run the bucket plan per shard (`_ragged_per_shard`).
    Bound placeholders (``bindings``) are replicated to every device.
    """
    ex = executor or default_executor()
    bindings = {k: np.asarray(v) for k, v in (bindings or {}).items()}
    if callable(fetches) and not isinstance(fetches, dsl.Tensor):
        return _fn_mesh(
            fetches, frame, mesh, trim=False, bindings=bindings, per_row=True
        )
    graph, fetch_list = _api._as_graph(fetches, fetch_names)
    overrides = _api._ph_overrides(
        graph, frame, feed_dict, block_level=False, bindings=bindings
    )
    summary = analyze_graph(graph, fetch_list, placeholder_shapes=overrides)
    _api._check_bindings(summary, bindings)
    mapping = _api._match_columns(
        summary, frame, feed_dict, block_level=False, bindings=bindings
    )
    params = sorted(summary.inputs)
    col_params = [p for p in params if p not in bindings]
    cols_used = [mapping[p] for p in col_params]
    out_names = [_base(f) for f in fetch_list]
    dense = all(frame.column(c).is_dense for c in cols_used)
    # same binding constraints as the local verb (api.map_rows)
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
    fn = build_callable(graph, fetch_list, params)

    if not dense:
        vfn = ex.cached(
            "vmap-rows",
            graph,
            fetch_list,
            params,
            lambda: jax.jit(jax.vmap(fn)),
        )
        per_out = _ragged_per_shard(
            vfn,
            [frame.column(c) for c in cols_used],
            frame.nrows,
            mesh,
            out_names_hint=out_names,
        )
        out_cols = [
            Column(
                n,
                per_out[n]
                if n in per_out
                else _api._empty_output(summary, n, drop_lead=False),
            )
            for n in out_names
        ]
        return _api._output_frame(frame, out_cols, append_input=True)

    ndev = mesh.devices.size
    main, tail, s = _split(frame, cols_used, ndev)
    in_axes = tuple(None if p in bindings else 0 for p in params)

    def _feeds(source: Dict[str, "np.ndarray"]) -> List:
        return [
            bindings[p] if p in bindings else source[mapping[p]]
            for p in params
        ]

    acc: Dict[str, List] = {n: [] for n in out_names}
    if s > 0:
        in_specs = _mesh_in_specs(
            params, bindings, main, col_of=mapping.__getitem__
        )
        spec_sig = ";".join(str(sp) for sp in in_specs)
        sharded = ex.cached(
            f"shmap-rows-{_mesh_sig(mesh)}-[{spec_sig}]",
            graph,
            fetch_list,
            params,
            lambda: jax.jit(
                shard_map(
                    jax.vmap(fn, in_axes=in_axes),
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=P("data"),
                )
            ),
        )
        outs = _mesh_call(
            "mesh.map_rows", graph.fingerprint(), s * ndev, ndev,
            sharded, *_on_mesh(mesh, in_specs, _feeds(main)),
        )
        maybe_check_numerics(fetch_list, outs, "map_rows (mesh shards)")
        for n, o in zip(out_names, outs):
            acc[n].append(o)
    if cols_used and tail[cols_used[0]].shape[0] > 0:
        # same cache key as the local verb: the tail program IS the
        # local vmap program, so the two paths share one executable
        bind_sig = ",".join(sorted(bindings))
        vfn = ex.cached(
            f"vmap-rows-[{bind_sig}]" if bindings else "vmap-rows",
            graph,
            fetch_list,
            params,
            lambda: jax.jit(jax.vmap(fn, in_axes=in_axes)),
        )
        outs = _mesh_call(
            "mesh.map_rows.tail", graph.fingerprint(),
            tail[cols_used[0]].shape[0], 1, vfn, *_feeds(tail),
        )
        maybe_check_numerics(fetch_list, outs, "map_rows (mesh tail)")
        for n, o in zip(out_names, outs):
            acc[n].append(o)
    out_cols = [
        Column(
            n,
            _concat_parts(mesh, parts)
            if parts
            else _api._empty_output(summary, n, drop_lead=False),
        )
        for n, parts in acc.items()
    ]
    return _api._output_frame(frame, out_cols, append_input=True)


# Compiled-program cache for the function front-end: the graph paths
# key on Graph.fingerprint via ex.cached, but a user function has no
# fingerprint — key on the function OBJECT (same discipline as jax.jit's
# own cache: a fresh lambda per call still recompiles, a named fn
# reused across calls does not).
_FN_MESH_CACHE: "OrderedDict[Tuple, Callable]" = OrderedDict()
_FN_MESH_LOCK = threading.Lock()
_FN_MESH_LIMIT = 64


def _fn_mesh_cached(key: Tuple, make: Callable) -> Callable:
    return lru_get_or_insert(
        _FN_MESH_CACHE, _FN_MESH_LOCK, key, make, _FN_MESH_LIMIT
    )[0]


def _fn_mesh(
    fn,
    frame: TensorFrame,
    mesh: Mesh,
    trim: bool,
    bindings: Dict[str, "np.ndarray"],
    per_row: bool,
) -> TensorFrame:
    """Function front-end for the mesh map verbs (map_blocks/map_rows).

    Mirrors `api._map_blocks_fn` / `api._map_rows_fn` validation, with
    the dense path run as one ``shard_map`` program over the ``data``
    axis (+ single-device tail) and, for per-row ragged columns, the
    bucket plan per shard.
    """
    verb = "map_rows" if per_row else "map_blocks"
    params = _api._fn_feed_columns(fn, frame, bound=set(bindings))
    unknown = sorted(set(bindings) - set(params))
    if unknown:
        raise ValueError(
            f"bindings {unknown} do not match any function parameter "
            f"(parameters: {params})"
        )
    col_params = [p for p in params if p not in bindings]

    def wrapped(*cells):
        return _api._fn_outputs_to_dict(fn(*cells), verb)

    dense = all(frame.column(p).is_dense for p in col_params)
    if per_row:
        if bindings and not col_params:
            raise ValueError(
                f"{verb}: every parameter is bound, so nothing varies per "
                "row; use map_blocks (or call the function directly)"
            )
        if bindings and not dense:
            raise ValueError(
                f"{verb}: bindings are not supported with ragged feed "
                "columns; densify the columns or bake the values as "
                "constants"
            )
        if not dense:
            vfn = _fn_mesh_cached(
                (fn, "vmap-ragged"),
                lambda: jax.jit(jax.vmap(wrapped)),
            )
            per_out = _ragged_per_shard(
                vfn,
                [frame.column(p) for p in col_params],
                frame.nrows,
                mesh,
            )
            out_cols = [Column(n, v) for n, v in per_out.items()]
            return _api._output_frame(frame, out_cols, append_input=True)
    else:
        _api._require_dense(frame, col_params, verb)

    in_axes = tuple(None if p in bindings else 0 for p in params)
    base = jax.vmap(wrapped, in_axes=in_axes) if per_row else wrapped
    ndev = mesh.devices.size
    main, tail, s = _split(frame, col_params, ndev)

    def _feeds(source: Dict[str, "np.ndarray"]) -> List:
        return [
            bindings[p] if p in bindings else source[p] for p in params
        ]

    def _validate(name: str, o, rows: int, expect: Optional[int]):
        """Lead-dim / row-count contract shared with the local verbs."""
        if not per_row:
            if o.ndim == 0:
                raise ValueError(
                    f"{verb}: output {name!r} must have a lead (row) dim"
                    + ("" if trim else "; use trim=True for reductions")
                )
            if not trim and o.shape[0] != rows:
                raise ValueError(
                    f"{verb}: output {name!r} does not preserve the block "
                    "row count; use trim=True"
                )
            if trim and expect is not None and o.shape[0] != expect:
                raise ValueError(
                    f"{verb}(trim): outputs disagree on row count"
                )

    acc: Dict[str, List] = {}
    block_sizes: List[int] = []
    if s > 0:
        in_specs = _mesh_in_specs(params, bindings, main)
        spec_sig = ";".join(str(sp) for sp in in_specs)
        sharded = _fn_mesh_cached(
            (fn, "shard", _mesh_sig(mesh), spec_sig, in_axes, per_row),
            lambda: jax.jit(
                shard_map(
                    base, mesh=mesh, in_specs=in_specs, out_specs=P("data")
                )
            ),
        )
        outs = sharded(*_on_mesh(mesh, in_specs, _feeds(main)))
        shard_out = None
        for name, o in outs.items():
            _validate(
                name, o, s * ndev,
                None if shard_out is None else shard_out * ndev,
            )
            if trim:
                shard_out = o.shape[0] // ndev
            acc.setdefault(name, []).append(o)
        block_sizes += [shard_out if trim else s] * ndev
    if col_params and tail[col_params[0]].shape[0] > 0:
        jfn = _fn_mesh_cached(
            (fn, "tail", in_axes, per_row), lambda: jax.jit(base)
        )
        outs = jfn(*_feeds(tail))
        tail_rows = tail[col_params[0]].shape[0]
        tail_out = None
        for name, o in outs.items():
            _validate(name, o, tail_rows, tail_out)
            if trim:
                tail_out = o.shape[0]
            acc.setdefault(name, []).append(o)
        block_sizes.append(tail_out if trim else tail_rows)
    if not acc:  # zero rows everywhere: names/dtypes from an abstract trace
        empties = _api._empty_fn_outputs(
            _fn_mesh_cached(
                (fn, "tail", in_axes, per_row), lambda: jax.jit(base)
            ),
            [
                bindings[p] if p in bindings
                else frame.column(p).values[:0]
                for p in params
            ],
        )
        acc = {n: [v] for n, v in empties.items()}
    out_cols = [
        Column(n, _concat_parts(mesh, parts)) for n, parts in acc.items()
    ]
    if trim:
        offsets = list(np.cumsum([0] + (block_sizes or [0])))
        return _api._output_frame(
            frame, out_cols, append_input=False, offsets=offsets
        )
    return _api._output_frame(
        frame, out_cols, append_input=True, offsets=frame.offsets
    )


# ---------------------------------------------------------------------------
# lazy fusion terminals (LazyFrame.force / LazyFrame.reduce_blocks, mesh=)
# ---------------------------------------------------------------------------


def fused_map_blocks(
    graph: Graph,
    frame: TensorFrame,
    mesh: Mesh,
    feed_map: Dict[str, str],
    fetch_edges: Sequence[str],
    out_names: Sequence[str],
    executor: Optional[Executor] = None,
) -> TensorFrame:
    """Force a lazy map plan on the mesh: the ENTIRE fused chain runs as
    ONE ``shard_map`` program over the ``data`` axis (+ the usual
    single-device remainder tail) — one dispatch where the eager chain
    paid one shard_map program per verb with intermediates materialized
    in HBM between them. ``feed_map`` wires fused-graph placeholders to
    base-frame columns; ``fetch_edges``/``out_names`` are the pending
    fused edges and their output column names (aligned)."""
    ex = executor or default_executor()
    feed_names = sorted(feed_map)
    cols_used = [feed_map[n] for n in feed_names]
    _api._require_dense(frame, cols_used, "lazy.force")
    ndev = mesh.devices.size
    main, tail, s, pad_rows = _bucketed_or_split(
        ex, frame, cols_used, ndev, graph, fetch_edges,
        {
            ph: frame.info[col].block_shape.rank
            for ph, col in feed_map.items()
        },
    )
    fn = build_callable(graph, list(fetch_edges), feed_names)
    acc: Dict[str, List] = {n: [] for n in out_names}
    if s > 0:
        in_specs = _mesh_in_specs(
            feed_names, {}, main, col_of=feed_map.__getitem__
        )
        spec_sig = ";".join(str(sp) for sp in in_specs)
        sharded = ex.cached(
            f"shmap-fused-{_mesh_sig(mesh)}-[{spec_sig}]",
            graph,
            fetch_edges,
            feed_names,
            lambda: jax.jit(
                shard_map(
                    fn, mesh=mesh, in_specs=in_specs, out_specs=P("data")
                )
            ),
        )
        outs = _mesh_call(
            "mesh.lazy.force", graph.fingerprint(), s * ndev, ndev,
            sharded, *_on_mesh(mesh, in_specs, [main[c] for c in cols_used]),
        )
        maybe_check_numerics(out_names, outs, "lazy fused map (mesh shards)")
        for n, o in zip(out_names, outs):
            if o.shape[0] != s * ndev:
                raise ValueError(
                    f"lazy plan output {n!r} does not preserve the row "
                    "count; trimmed/reducing stages cannot be part of a "
                    "lazy map plan"
                )
            acc[n].append(o[: frame.nrows] if pad_rows else o)
    if cols_used and tail[cols_used[0]].shape[0] > 0:
        tfn = ex.callable_for(graph, fetch_edges, feed_names)
        outs = _mesh_call(
            "mesh.lazy.force.tail", graph.fingerprint(),
            tail[cols_used[0]].shape[0], 1,
            tfn, *[tail[c] for c in cols_used],
        )
        maybe_check_numerics(out_names, outs, "lazy fused map (mesh tail)")
        trows = tail[cols_used[0]].shape[0]
        for n, o in zip(out_names, outs):
            if o.ndim == 0 or o.shape[0] != trows:
                raise ValueError(
                    f"lazy plan output {n!r} does not preserve the row "
                    "count; trimmed/reducing stages cannot be part of a "
                    "lazy map plan"
                )
            acc[n].append(o)
    out_cols = [
        Column(n, _concat_parts(mesh, acc[n])) for n in out_names if acc[n]
    ]
    shadow = set(out_names)
    cols = out_cols + [
        frame.column(c) for c in frame.columns if c not in shadow
    ]
    return TensorFrame(cols, frame.offsets)


def fused_reduce_blocks(
    fused_graph: Graph,
    fused_fetches: Sequence[str],
    feed_map: Dict[str, str],
    frame: TensorFrame,
    rgraph: Graph,
    rfetch: Sequence[str],
    rfeed_names: Sequence[str],
    feed_src: Sequence[int],
    mesh: Mesh,
    executor: Optional[Executor] = None,
) -> Tuple:
    """Terminal fused reduce on the mesh: shard-local map chain + block
    reduce run as ONE ``shard_map`` program (fused graph), the gathered
    partials re-reduce through the PLAIN reduce graph inside the same
    program — the `reduce_blocks` local_then_gather topology with the
    whole pending pipeline in the local stage. Returns the final fetch
    tuple (in ``rfetch`` order); the caller unwraps."""
    ex = executor or default_executor()
    feed_names = sorted(feed_map)
    cols_used = [feed_map[n] for n in feed_names]
    _api._require_dense(frame, cols_used, "reduce_blocks")
    ndev = mesh.devices.size
    main, tail, s = _split(frame, cols_used, ndev)
    fn = build_callable(fused_graph, list(fused_fetches), feed_names)
    rfn = build_callable(rgraph, list(rfetch), list(rfeed_names))

    partials: List[Tuple] = []
    if s > 0:
        def local_then_gather(*cols):
            part = fn(*cols)
            gathered = [
                lax.all_gather(part[i], "data", axis=0, tiled=False)
                for i in feed_src
            ]
            return tuple(rfn(*gathered))

        in_specs = _mesh_in_specs(
            feed_names, {}, main, col_of=feed_map.__getitem__
        )
        sharded = ex.cached(
            f"shred-fused-{_mesh_sig(mesh)}",
            fused_graph,
            fused_fetches,
            feed_names,
            lambda: jax.jit(
                shard_map(
                    local_then_gather,
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=P(),
                    check_vma=False,
                )
            ),
        )
        outs = _mesh_call(
            "mesh.reduce_blocks.fused", fused_graph.fingerprint(),
            s * ndev, ndev, sharded,
            *_on_mesh(mesh, in_specs, [main[c] for c in cols_used]),
        )
        partials.append(tuple(outs))
    if cols_used and tail[cols_used[0]].shape[0] > 0:
        tfn = ex.callable_for(fused_graph, fused_fetches, feed_names)
        outs = _mesh_call(
            "mesh.reduce_blocks.fused.tail", fused_graph.fingerprint(),
            tail[cols_used[0]].shape[0], 1,
            tfn, *[tail[c] for c in cols_used],
        )
        partials.append(tuple(outs))
    if not partials:
        raise ValueError("reduce_blocks on an empty frame")
    if len(partials) == 1:
        final = tuple(partials[0])
    else:
        crfn = ex.callable_for(rgraph, rfetch, rfeed_names)
        stacked = [
            _api._stack_parts([p[i] for p in partials]) for i in feed_src
        ]
        final = tuple(crfn(*stacked))
    maybe_check_numerics(list(rfetch), list(final), "reduce_blocks (mesh, fused)")
    return final


# ---------------------------------------------------------------------------
# reduce_blocks
# ---------------------------------------------------------------------------


def reduce_blocks(
    fetches,
    frame: TensorFrame,
    mesh: Mesh,
    feed_dict: Optional[Dict[str, str]] = None,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
):
    """Distributed reduce: shard-local reduce + all-gather combine on ICI."""
    ex = executor or default_executor()
    graph, fetch_list = _api._as_graph(fetches, fetch_names)
    overrides = _api._ph_overrides(graph, frame, feed_dict, block_level=True)
    summary = analyze_graph(graph, fetch_list, placeholder_shapes=overrides)
    _api._validate_reduce_blocks(summary, fetch_list)
    mapping = _api._match_columns(summary, frame, feed_dict, block_level=True)
    _api._require_dense(frame, list(mapping.values()), "reduce_blocks")

    feed_names = sorted(summary.inputs)
    cols_used = [mapping[n] for n in feed_names]
    ndev = mesh.devices.size
    fn = build_callable(graph, fetch_list, feed_names)
    # Both mesh reduce shapes drift with nrows — the sharded main
    # program re-specializes per distinct nrows//ndev and the remainder
    # tail per distinct nrows%ndev. For classified monoid graphs the
    # main shards pad to the bucket ladder with per-shard valid counts
    # masked inside the shard_map program (Mean excluded: regrouping
    # shard boundaries would change the equal-weight partial combine),
    # and the tail routes through the SAME masked bucketed program as
    # the local verb (shared cache entry) — both bounded to the ladder.
    from .. import shape_policy as _sp

    mask_plan = (
        _sp.masked_reduce_plan(graph, fetch_list, summary)
        if _sp.enabled(ex)
        else None
    )
    bucket_shards = (
        mask_plan is not None
        and "mean" not in mask_plan.combiners
        and cols_used
        and frame.nrows > 0
    )
    if bucket_shards and not (
        _sp.mesh_shard_plan(frame.nrows, ndev)[1] > 0
    ).all():
        # An all-pad shard emits the BARE reduction identity, and the
        # gathered combine re-feeds partials through the whole graph —
        # identity values are neutral there only when each reduce
        # consumes its placeholder DIRECTLY (Max(Abs(x)) would turn the
        # -inf identity into +inf). Same reasoning as streaming's
        # require_direct tree-fold gate; indirect graphs fall back to
        # the unbucketed shards + masked tail. Decided on the plan's
        # pure arithmetic, BEFORE paying for any padded column copy.
        bucket_shards = (
            _api._chunk_combiners(
                graph, fetch_list, summary, require_direct=True
            )
            is not None
        )
    if bucket_shards:
        main, tail, s, shard_valids = _sp.pad_mesh_shards(
            frame, cols_used, ndev
        )
    else:
        main, tail, s = _split(frame, cols_used, ndev)
    # Combining partials re-feeds fn: outputs arrive in FETCH order but
    # fn's positional args are the SORTED feed names, and with several
    # fetches those orders differ (x/n fetches sort as n_input, x_input)
    # — feeding positionally would silently swap results between
    # fetches. feed_src[j] = index of the fetch whose partial feeds
    # feed_names[j] (the host path re-keys by name the same way).
    fetch_of_feed = {_base(f) + "_input": i for i, f in enumerate(fetch_list)}
    feed_src = [fetch_of_feed[n] for n in feed_names]

    partials: List[Tuple[np.ndarray, ...]] = []
    if s > 0:
        col_specs = tuple(
            P("data", *([None] * (main[c].ndim - 1))) for c in cols_used
        )
        if bucket_shards:
            def make_masked_sharded():
                mraw = _sp.build_masked_reduce(graph, mask_plan, feed_names)

                def local_then_gather_masked(valid, *cols):
                    # valid arrives as this shard's (1,) slice of the
                    # per-shard counts; build_masked_reduce squeezes it
                    part = mraw(valid, *cols)
                    gathered = [
                        lax.all_gather(part[i], "data", axis=0, tiled=False)
                        for i in feed_src
                    ]
                    return tuple(fn(*gathered))

                return jax.jit(
                    shard_map(
                        local_then_gather_masked,
                        mesh=mesh,
                        in_specs=(P("data"),) + col_specs,
                        out_specs=P(),
                        check_vma=False,
                    )
                )

            sharded = ex.cached(
                f"shred-bkt-{_mesh_sig(mesh)}",
                graph,
                fetch_list,
                feed_names,
                make_masked_sharded,
            )
            outs = _mesh_call(
                "mesh.reduce_blocks", graph.fingerprint(), s * ndev, ndev,
                sharded, shard_valids,
                *_on_mesh(mesh, col_specs, [main[c] for c in cols_used]),
            )
        else:
            def local_then_gather(*cols):
                part = fn(*cols)
                gathered = [
                    lax.all_gather(part[i], "data", axis=0, tiled=False)
                    for i in feed_src
                ]
                final = fn(*gathered)
                return tuple(final)

            sharded = ex.cached(
                f"shred-{_mesh_sig(mesh)}",
                graph,
                fetch_list,
                feed_names,
                lambda: jax.jit(
                    shard_map(
                        local_then_gather,
                        mesh=mesh,
                        in_specs=col_specs,
                        out_specs=P(),  # combined result is replicated
                        check_vma=False,
                    )
                ),
            )
            outs = _mesh_call(
                "mesh.reduce_blocks", graph.fingerprint(), s * ndev, ndev,
                sharded,
                *_on_mesh(mesh, col_specs, [main[c] for c in cols_used]),
            )
        partials.append(tuple(outs))
    if cols_used and tail[cols_used[0]].shape[0] > 0:
        t = [tail[c] for c in cols_used]
        if mask_plan is not None:
            mfn = _sp.masked_callable(
                ex, graph, fetch_list, feed_names, mask_plan
            )
            outs = _mesh_call(
                "mesh.reduce_blocks.tail", graph.fingerprint(),
                t[0].shape[0], 1,
                _sp.dispatch_masked, mfn, t, t[0].shape[0],
            )
        else:
            tfn = ex.callable_for(graph, fetch_list, feed_names)
            outs = _mesh_call(
                "mesh.reduce_blocks.tail", graph.fingerprint(),
                t[0].shape[0], 1, tfn, *t,
            )
        partials.append(tuple(outs))
    if not partials:
        raise ValueError("reduce_blocks on an empty frame")
    if len(partials) == 1:
        final = tuple(partials[0])
    else:
        # device-resident combine, same discipline as the host path:
        # in-process partials (jax.Array) stack on device and re-reduce
        # without a host round-trip; native-executor partials stay on
        # host (see api._stack_parts on the double-client hazard)
        tfn = ex.callable_for(graph, fetch_list, feed_names)
        stacked = [
            _api._stack_parts([p[i] for p in partials]) for i in feed_src
        ]
        final = tuple(tfn(*stacked))
    maybe_check_numerics(fetch_list, list(final), "reduce_blocks (mesh)")
    if len(fetch_list) == 1:
        return final[0]
    return {_base(f): v for f, v in zip(fetch_list, final)}


# ---------------------------------------------------------------------------
# reduce_rows
# ---------------------------------------------------------------------------


def reduce_rows(
    fetches,
    frame: TensorFrame,
    mesh: Mesh,
    feed_dict: Optional[Dict[str, str]] = None,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
):
    """Distributed pairwise fold: scan per shard, gather, fold partials."""
    ex = executor or default_executor()
    graph, fetch_list = _api._as_graph(fetches, fetch_names)
    overrides = _api._ph_overrides(graph, frame, feed_dict, block_level=False)
    summary = analyze_graph(graph, fetch_list, placeholder_shapes=overrides)
    _api._validate_reduce_rows(summary, fetch_list)
    mapping = _api._match_columns(summary, frame, feed_dict, block_level=False)
    _api._require_dense(frame, list(mapping.values()), "reduce_rows")

    bases = [_base(f) for f in fetch_list]
    feed_names = [b + s for b in bases for s in ("_1", "_2")]
    cols_used = [mapping[b + "_1"] for b in bases]
    ndev = mesh.devices.size
    main, tail, s = _split(frame, cols_used, ndev)
    pair = build_callable(graph, fetch_list, feed_names)

    def fold_rows(cols: Tuple):
        carry0 = tuple(c[0] for c in cols)
        xs = tuple(c[1:] for c in cols)

        def step(carry, xrow):
            feeds = []
            for i in range(len(bases)):
                feeds.extend((carry[i], xrow[i]))
            return tuple(pair(*feeds)), None

        carry, _ = lax.scan(step, carry0, xs)
        return carry

    partials: List[Tuple[np.ndarray, ...]] = []
    if s >= 1 and ndev > 0:
        def shard_fold(*cols):
            # fold_rows handles s == 1 too (zero-length scan returns the
            # carry unchanged) — no size-dependent branch may live in
            # this closure, because the compiled fn is CACHED by
            # (graph, ndev) and a branch captured at first trace would
            # silently misapply to later calls with a different shard
            # size
            local = fold_rows(cols)
            gathered = tuple(
                lax.all_gather(p, "data", axis=0, tiled=False) for p in local
            )
            return fold_rows(gathered)

        in_specs = tuple(
            P("data", *([None] * (main[c].ndim - 1))) for c in cols_used
        )
        sharded = ex.cached(
            f"shfold-{_mesh_sig(mesh)}",
            graph,
            fetch_list,
            feed_names,
            lambda: jax.jit(
                shard_map(
                    shard_fold,
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=P(),
                    check_vma=False,
                )
            ),
        )
        outs = _mesh_call(
            "mesh.reduce_rows", graph.fingerprint(), s * ndev, ndev,
            sharded, *_on_mesh(mesh, in_specs, [main[c] for c in cols_used]),
        )
        partials.append(tuple(np.asarray(o) for o in outs))

    # tail folds + partial combine share ONE cached program (jit
    # re-specializes per lead dim) instead of building a fresh
    # jax.jit closure per call (round-3 verdict: every other mesh
    # program was cached; these two leaked a compile per invocation)
    def _jfold():
        return ex.cached(
            "jfold",
            graph,
            fetch_list,
            feed_names,
            lambda: jax.jit(lambda *cols: fold_rows(cols)),
        )

    if cols_used and tail[cols_used[0]].shape[0] > 0:
        t = [tail[c] for c in cols_used]
        if t[0].shape[0] == 1:
            partials.append(tuple(np.asarray(x[0]) for x in t))
        else:
            partials.append(tuple(np.asarray(o) for o in _jfold()(*t)))
    if not partials:
        raise ValueError("reduce_rows on an empty frame")
    if len(partials) == 1:
        final = partials[0]
    else:
        stacked = [
            np.stack([p[i] for p in partials]) for i in range(len(bases))
        ]
        final = tuple(np.asarray(o) for o in _jfold()(*stacked))
    maybe_check_numerics(bases, list(final), "reduce_rows (mesh)")
    if len(bases) == 1:
        return final[0]
    return dict(zip(bases, final))


# ---------------------------------------------------------------------------
# aggregate
# ---------------------------------------------------------------------------


# Shared with the host segment path so both overflow the same way.
_gid_dtype = _api._gid_dtype


def aggregate(
    fetches,
    grouped: "_api.GroupedFrame",
    mesh: Mesh,
    feed_dict: Optional[Dict[str, str]] = None,
    fetch_names: Optional[Sequence[str]] = None,
    executor: Optional[Executor] = None,
) -> TensorFrame:
    """Distributed keyed aggregation.

    Fast path for sum-shaped graphs (every fetch = `Sum` over the lead axis
    of its placeholder): shard-local `segment_sum` into a dense
    (num_keys, ...) table + `psum` over ICI — two collectives total,
    replacing the reference's UDAF buffer/compact/shuffle machinery.
    Other graphs classified as `Reduce(rowwise(placeholder), axis=0)`
    run the chunked plan with the chunk stage shard_mapped over the mesh
    (`_aggregate_mesh_general`); anything else falls back to the host
    exact plan.
    """
    frame = grouped.frame
    graph, fetch_list = _api._as_graph(fetches, fetch_names)
    if not _all_fetches_are_lead_sums(graph, fetch_list):
        return _aggregate_mesh_general(
            graph, grouped, mesh, feed_dict, fetch_list, executor
        )
    ex = executor or default_executor()
    overrides = _api._ph_overrides(graph, frame, feed_dict, block_level=True)
    summary = analyze_graph(graph, fetch_list, placeholder_shapes=overrides)
    _api._validate_reduce_blocks(summary, fetch_list)
    mapping = _api._match_columns(summary, frame, feed_dict, block_level=True)
    _api._require_dense(frame, list(mapping.values()), "aggregate")

    # host: factorize keys once (global key table)
    from ..frame import factorize_keys

    key_arrays = [frame.column(k).host_values() for k in grouped.keys]
    key_out, inverse = factorize_keys(grouped.keys, key_arrays)
    num_keys = len(next(iter(key_out.values())))
    gid = inverse.astype(_gid_dtype(num_keys))

    feed_names = sorted(summary.inputs)
    cols_used = [mapping[n] for n in feed_names]
    ndev = mesh.devices.size
    n = frame.nrows
    s = n // ndev

    # pow2-bucketed segment-table size: a DATA-dependent num_keys in the
    # cache key would mint a permanent compiled program per distinct key
    # cardinality (code-review r4: unbounded growth in a long-lived
    # service whose key count drifts); padding the dense table to the
    # next power of two caps distinct programs at O(log max_keys), and
    # the pad rows (no gid ever points at them) are sliced off below
    padded_keys = 1 << max(0, int(num_keys) - 1).bit_length()

    def seg_psum(gids, *cols):
        outs = []
        for c in cols:
            seg = jax.ops.segment_sum(c, gids, padded_keys)
            outs.append(lax.psum(seg, "data"))
        return tuple(outs)

    results: Dict[str, np.ndarray] = {}
    # seg_psum returns one output per FEED (sorted feed_names order); the
    # base receiving each output is the feed's x_input -> x pairing, NOT
    # fetch_list order (they differ with several fetches)
    bases = [n[: -len("_input")] for n in feed_names]
    main_cols = [frame.column(c).values[: s * ndev] for c in cols_used]
    tail_cols = [frame.column(c).values[s * ndev :] for c in cols_used]
    acc = [np.zeros(0)] * len(bases)
    if s > 0:
        in_specs = (P("data"),) + tuple(
            P("data", *([None] * (c.ndim - 1))) for c in main_cols
        )
        # cached like every other mesh program (round-3 verdict: this
        # closure recompiled on every aggregate(mesh=...) call); the
        # padded table size shapes the program, so it keys the entry
        sharded = ex.cached(
            f"shagg-sum-{_mesh_sig(mesh)}-{padded_keys}",
            graph,
            fetch_list,
            feed_names,
            lambda: jax.jit(
                shard_map(
                    seg_psum,
                    mesh=mesh,
                    in_specs=in_specs,
                    out_specs=P(),
                    check_vma=False,
                )
            ),
        )
        outs = _mesh_call(
            "mesh.aggregate.segment", graph.fingerprint(), s * ndev, ndev,
            sharded, *_on_mesh(mesh, in_specs, [gid[: s * ndev], *main_cols]),
        )
        acc = [np.asarray(o)[:num_keys] for o in outs]
    if tail_cols and tail_cols[0].shape[0] > 0:
        touts = [
            np.asarray(jax.ops.segment_sum(jnp.asarray(c), gid[s * ndev :], num_keys))
            for c in tail_cols
        ]
        acc = [a + t if a.size else t for a, t in zip(acc, touts)]
    maybe_check_numerics(bases, acc, "aggregate (mesh segment fast path)")
    for b, a in zip(bases, acc):
        results[b] = a

    cols = [Column(k, v) for k, v in key_out.items()]
    cols += [Column(b, results[b]) for b in sorted(bases)]
    return TensorFrame(cols)


def _aggregate_mesh_general(
    graph: Graph,
    grouped: "_api.GroupedFrame",
    mesh: Mesh,
    feed_dict: Optional[Dict[str, str]],
    fetch_list: List[str],
    executor: Optional[Executor],
) -> TensorFrame:
    """Mesh aggregation for any chunk-safe graph (`api._chunk_combiners`).

    Round 1 only meshed `Sum(x_input, axis=0)` graphs and silently fell
    back to the host path for everything else. Here every fetch
    classified as `Reduce(rowwise(placeholder), axis=0)` — Min/Max/Mean/
    Prod/Sum over arbitrary row-local transforms — runs the pow2
    chunk-decomposition plan (`api._aggregate_chunked`) with the chunk
    stage `shard_map`ped over the mesh's ``data`` axis: per-chunk
    reductions execute devices-wide with zero collectives (chunks are
    independent), and partials combine host-side with the DERIVED monoid
    (size-weighted for Mean), so results are exact. Unclassifiable
    graphs fall back to the host exact plan rather than risking a wrong
    partial-combine — the correctness-first choice the reference makes
    with its driver-funneled reduce.
    """
    ex = executor or default_executor()
    frame = grouped.frame
    overrides = _api._ph_overrides(graph, frame, feed_dict, block_level=True)
    summary = analyze_graph(graph, fetch_list, placeholder_shapes=overrides)
    combiners = _api._chunk_combiners(graph, fetch_list, summary)
    if combiners is None:
        return _api.aggregate(
            graph, grouped, feed_dict, fetch_names=fetch_list,
            executor=executor,
        )
    _api._validate_reduce_blocks(summary, fetch_list)
    mapping = _api._match_columns(summary, frame, feed_dict, block_level=True)
    _api._require_dense(frame, list(mapping.values()), "aggregate")

    feed_names = sorted(summary.inputs)
    bases = [_base(f) for f in fetch_list]
    key_out, num_groups, counts, starts, col_data = _api._group_plan(
        grouped, mapping, feed_names
    )

    vfn = jax.vmap(build_callable(graph, fetch_list, feed_names))
    local = ex.cached(
        "vmap-agg", graph, fetch_list, feed_names, lambda: jax.jit(vfn)
    )
    ndev = mesh.devices.size
    # chunk feeds are (n, size, *cell) for every stage, so ONE shard_map
    # over the lead (chunk) axis serves both the chunk and combine stages
    sharded = ex.cached(
        f"shagg-{_mesh_sig(mesh)}",
        graph,
        fetch_list,
        feed_names,
        lambda: jax.jit(
            shard_map(
                vfn,
                mesh=mesh,
                in_specs=tuple(P("data") for _ in feed_names),
                out_specs=tuple(P("data") for _ in fetch_list),
                check_vma=False,
            )
        ),
    )

    def run(feeds):
        # pad_quantum=ndev makes every chunk-stage lead ndev * 2^k, so
        # this always shards on any device count, pow2 or not
        lead = feeds[0].shape[0]
        if lead >= ndev and lead % ndev == 0:
            return _mesh_call(
                "mesh.aggregate.chunk", graph.fingerprint(), lead, ndev,
                sharded,
                *_on_mesh(mesh, [P("data")] * len(feeds), feeds),
            )
        return local(*feeds)

    results = _api._aggregate_chunked(
        run,
        feed_names,
        col_data,
        counts,
        starts,
        num_groups,
        bases,
        combiners,
        pad_quantum=ndev,
        program=graph.fingerprint(),
    )
    if num_groups == 0:  # empty frame: zero-row outputs from analysis
        results = {
            b: _api._empty_output(summary, b, drop_lead=False) for b in bases
        }
    return _api._keyed_output(key_out, results, bases)


def _all_fetches_are_lead_sums(graph: Graph, fetch_list: List[str]) -> bool:
    """True when every fetch is `Sum(x_input, reduction_indices=[0])` —
    the segment_sum/psum fast-path pattern."""
    for f in fetch_list:
        try:
            node = graph[_base(f)]
        except KeyError:
            return False
        if node.op != "Sum":
            return False
        data_in = node.data_inputs()
        if len(data_in) != 2:
            return False
        src, _ = data_in[0]
        if graph[src].op not in ("Placeholder", "PlaceholderV2"):
            return False
        idx_node = graph[data_in[1][0]]
        if idx_node.op != "Const":
            return False
        axes = idx_node.attrs["value"].value.to_numpy().ravel().tolist()
        if axes != [0]:
            return False
    return True
