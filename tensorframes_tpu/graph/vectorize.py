"""Auto-batching pass for functionalized per-row control flow.

The reference ran arbitrary GraphDefs because libtensorflow interpreted
dataflow control flow per row; our XLA port functionalizes
`tf.cond`/`tf.while_loop` into `_Cond`/`_While` pseudo-nodes
(`graph.control_flow`), and the conservative row-local classifier
(`aggregate._rowwise_transform`) used to disqualify any graph containing
them — branchy per-row workloads lost the bucket ladder, OOM splitting,
serving batching, and the GlobalFrame one-dispatch SPMD path.

This module closes that gap with the lowering "Auto-Vectorizing
TensorFlow Graphs" describes (PAPERS.md):

* `_Cond` whose branch subgraphs are row-local lowers to
  both-branches-evaluated + a select on the batched predicate
  (`select_cond`). Legal because `freeze_variables` already guarantees
  branch bodies are side-effect-free pure functions.
* `_While` lowers to a convergence-masked fixed point (`masked_while`):
  one `lax.while_loop` iterates until EVERY row's predicate is false;
  rows that converged early are carried through later iterations
  unchanged by a per-row boolean mask folded into the carry. The trip
  count is bounded by the same static-shape contract scalar loops obey.

`subgraphs_row_local` is the classification hook `_rowwise_transform`
calls for control-flow nodes: a `_Cond`/`_While` counts as row-local
exactly when every branch/cond/body subgraph passes the SAME row-local
walk at the enclosing graph's lead rank (subgraph feeds are slices of
the outer row axis, so they inherit it). That one predicate threads the
fast path through every consumer of `shape_policy.rowwise_fetches`:
`api.map_blocks` bucketing, `api.map_rows` bucketed vmapped dispatch,
`lazy` fusion, `globalframe` SPMD routing, and the serving batchability
probe.

Everything is gated behind ``config.row_vectorize`` (env
``TFS_ROW_VECTORIZE``, default on). Graphs whose branches or carries are
not row-local fall back to the historical unbatched path; every decision
is counted by reason in the module ledger (`state()` /
`tfs.diagnostics()`) and in the always-live Prometheus counters
``row_vectorize_lowered{kind=}`` / ``row_vectorize_fallbacks{reason=}``.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Control-flow pseudo-nodes this pass can vectorize, mapped to their
# subgraph attr keys and the fallback-reason label each subgraph gets
# when it fails the row-local walk.
_SUB_ATTRS = {
    "_Cond": (("cond_then", "cond-branch"), ("cond_else", "cond-branch")),
    "_While": (("while_cond", "while-cond"), ("while_body", "while-body")),
}

#: Node ops `aggregate._rowwise_transform` defers to `subgraphs_row_local`
#: instead of rejecting outright.
CONTROL_OPS = frozenset(_SUB_ATTRS)

_state_lock = threading.Lock()
_stats: Dict[str, Dict[str, int]] = {"lowered": {}, "fallbacks": {}}


def enabled() -> bool:
    from .. import config

    return bool(config.get().row_vectorize)


def note_lowered(kind: str) -> None:
    """One masked dense lowering traced (kind: ``cond`` | ``while``).

    Fires at trace time — once per compiled specialization, not per
    dispatch — which is what "how many programs went through the
    vectorizer" means."""
    from ..utils import telemetry as _tele

    with _state_lock:
        _stats["lowered"][kind] = _stats["lowered"].get(kind, 0) + 1
    _tele.counter_inc("row_vectorize_lowered", 1.0, kind=kind)


def note_fallback(reason: str) -> None:
    """One graph kept OFF the vectorized fast path, by reason. Counts
    classification events (a graph probed by several consumers counts
    once per probe), mirroring `global_fallbacks` semantics."""
    from ..utils import telemetry as _tele

    with _state_lock:
        _stats["fallbacks"][reason] = _stats["fallbacks"].get(reason, 0) + 1
    _tele.counter_inc("row_vectorize_fallbacks", 1.0, reason=reason)


def state() -> Dict:
    """Snapshot for `tfs.diagnostics()`: lowerings by kind, fallbacks by
    reason."""
    with _state_lock:
        return {
            "lowered": dict(_stats["lowered"]),
            "fallbacks": dict(_stats["fallbacks"]),
        }


def reset_state() -> None:
    with _state_lock:
        _stats["lowered"] = {}
        _stats["fallbacks"] = {}


def lift_to_block_level(graph):
    """Stamp a leading unknown row axis onto every placeholder's
    declared shape, in place, and return the graph.

    TensorFlow cannot author per-row control flow at block level —
    `tf.cond`/`tf.while_loop` demand a SCALAR predicate — so a
    block-level branchy program is authored per row (cell-level
    placeholders, scalar predicates) and lifted: after the lift the
    predicates carry the block's row axis and the masked dense
    lowerings in this module take over. This is how branchy serving
    endpoints and block-level branchy maps are built (the tests use
    it)."""
    from ..proto.graphdef import AttrValue
    from ..schema import Shape

    for ph in graph.placeholders():
        cell = ph.shape_attr
        dims = (None,) + tuple(cell.dims) if cell is not None else (None,)
        ph.attrs["shape"] = AttrValue.of_shape(Shape(dims))
    return graph


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


def subgraphs_row_local(graph, node, lead_rank: int) -> bool:
    """True when every subgraph of control-flow ``node`` is row-local at
    the enclosing graph's ``lead_rank``.

    Subgraph placeholders (``__sw{k}``/``__var{i}``/``__cap{j}``) carry
    slices of the outer graph's row axis, so each one is checked at the
    OUTER lead rank; nested control flow recurses through the same walk.
    Counts a fallback reason on every rejection so branchy graphs that
    stay off the fast path are visible in diagnostics."""
    if not enabled():
        note_fallback("disabled")
        return False
    from ..aggregate import _rowwise_transform

    for attr_key, label in _SUB_ATTRS[node.op]:
        key = node.attr(attr_key)
        key = key.decode() if isinstance(key, bytes) else key
        sub = getattr(graph, "subgraphs", {}).get(key)
        if sub is None:
            note_fallback(f"{label}-missing")
            return False
        if not _rowwise_transform(
            sub.graph, list(sub.fetches), lambda _name: lead_rank
        ):
            note_fallback(f"{label}-not-row-local")
            return False
    return True


# ---------------------------------------------------------------------------
# masked dense lowerings (called from ops/control.py when the traced
# predicate is batched — i.e. the per-row graph is executing at block
# level, where the predicate carries the block's row axis)
# ---------------------------------------------------------------------------


def _lowering_error(msg: str):
    from ..ops.registry import GraphLoweringError

    return GraphLoweringError(msg)


def _pred_rows(node, shape) -> int:
    """Row count of a batched predicate (shape-only: works on avals,
    tracers, and concrete arrays alike)."""
    shape = tuple(shape)
    if len(shape) < 1 or math.prod(shape) != shape[0]:
        raise _lowering_error(
            f"{node.op} (node {node.name!r}) predicate has shape "
            f"{shape}; a vectorized predicate must carry exactly one "
            "value per row (lead axis only, unit trailing dims)"
        )
    return int(shape[0])


def _flat_rows(node, pred) -> Tuple[int, jnp.ndarray]:
    """Interpret a batched predicate as one boolean per row."""
    p = jnp.asarray(pred)
    n = _pred_rows(node, p.shape)
    return n, p.reshape((n,)).astype(bool)


def select_cond(node, pred, then_outs, else_outs) -> Tuple:
    """Both-branches-evaluated + per-output select on the batched
    predicate. Branch outputs may sit below the lead rank (a per-row
    scalar/vector the branch computed identically for every row); they
    broadcast against the row-axis mask like any sub-lead constant in a
    row-local graph."""
    n, mask = _flat_rows(node, pred)
    outs = []
    for i, (t, e) in enumerate(zip(then_outs, else_outs)):
        t, e = jnp.asarray(t), jnp.asarray(e)
        if t.dtype != e.dtype:
            raise _lowering_error(
                f"_Cond (node {node.name!r}) output {i}: then-branch "
                f"dtype {t.dtype} != else-branch dtype {e.dtype}; both "
                "branches of a cond must produce the same dtype"
            )
        rank = max(t.ndim, e.ndim, 1)
        m = mask.reshape((n,) + (1,) * (rank - 1))
        try:
            jnp.broadcast_shapes(m.shape, t.shape, e.shape)
        except ValueError:
            raise _lowering_error(
                f"_Cond (node {node.name!r}) output {i}: then-branch "
                f"shape {t.shape} and else-branch shape {e.shape} do not "
                f"broadcast against the {n}-row predicate; both branches "
                "must produce per-row-compatible shapes"
            ) from None
        outs.append(jnp.where(m, t, e))
    note_lowered("cond")
    return tuple(outs)


def check_branch_avals(node, tfn, efn, operands) -> None:
    """Scalar-predicate pre-check: `lax.cond` demands identical output
    avals from both branches; diagnose the mismatch by output index and
    shape/dtype instead of surfacing XLA's raw trace error."""
    touts = jax.eval_shape(lambda *o: tuple(tfn(*o)), *operands)
    eouts = jax.eval_shape(lambda *o: tuple(efn(*o)), *operands)
    for i, (t, e) in enumerate(zip(touts, eouts)):
        if t.shape != e.shape or t.dtype != e.dtype:
            raise _lowering_error(
                f"_Cond (node {node.name!r}) output {i}: then-branch "
                f"produces {t.dtype}{list(t.shape)} but else-branch "
                f"produces {e.dtype}{list(e.shape)}; both branches of a "
                "cond must produce the same shape and dtype"
            )


def check_while_carry(node, body_fn, carry, n_vars: int) -> None:
    """Scalar-path pre-check: `lax.while_loop` demands the body preserve
    every carry aval exactly; name the offending carry (loop var vs
    invariant capture, original input edge, shapes/dtypes) instead of
    surfacing XLA's raw trace error."""
    outs = jax.eval_shape(lambda *c: tuple(body_fn(*c)), *carry)
    for i, (c, o) in enumerate(zip(carry, outs)):
        if o.shape != c.shape or o.dtype != c.dtype:
            raise _lowering_error(_carry_drift_msg(node, i, n_vars, c, o))


def _carry_drift_msg(node, i, n_vars, c, o) -> str:
    kind = "loop var" if i < n_vars else "invariant capture"
    edge = node.inputs[i] if i < len(node.inputs) else "<missing>"
    return (
        f"_While (node {node.name!r}) carry {i} ({kind}, input "
        f"{edge!r}) drifts from {jnp.dtype(c.dtype)}{list(c.shape)} to "
        f"{jnp.dtype(o.dtype)}{list(o.shape)} across iterations; loop "
        "carries must keep a fixed shape and dtype"
    )


def masked_while(node, carry, n_vars: int, cond_fn, body_fn, pred0) -> Tuple:
    """Lower a `_While` with a batched predicate to ONE dense
    `lax.while_loop` over the whole block.

    Semantics: every carry broadcasts to the row axis (rows evolve
    independently); the loop iterates while ANY row's predicate holds;
    a per-row convergence mask in the carry freezes rows whose predicate
    went false, so ragged per-row trip counts execute in
    max-trips-over-rows dense iterations. Pad rows (shape bucketing
    replicates the last valid row) converge exactly when their source
    row does, so the bucket ladder stays sound."""
    n = _pred_rows(node, pred0.shape)
    carry = tuple(_broadcast_lead(c, n) for c in carry)

    # loud-naming pre-check (same contract as the scalar path, relaxed
    # to broadcast-compatibility: a body output may sit sub-lead and be
    # spread across rows by the mask select)
    outs = jax.eval_shape(lambda *c: tuple(body_fn(*c)), *carry)
    for i, (c, o) in enumerate(zip(carry, outs)):
        ok = o.dtype == c.dtype
        if ok:
            try:
                ok = jnp.broadcast_shapes(o.shape, c.shape) == c.shape
            except ValueError:
                ok = False
        if not ok:
            raise _lowering_error(_carry_drift_msg(node, i, n_vars, c, o))

    def _pred(c) -> jnp.ndarray:
        p = jnp.asarray(cond_fn(*c)[0]).astype(bool)
        if p.size == 1:
            return jnp.broadcast_to(p.reshape(()), (n,))
        return _flat_rows(node, p)[1]

    def _step(state):
        active, c = state
        new = tuple(jnp.asarray(v) for v in body_fn(*c))
        sel = tuple(
            jnp.where(
                active.reshape((n,) + (1,) * (old.ndim - 1)), nv, old
            )
            for nv, old in zip(new, c)
        )
        return (jnp.logical_and(active, _pred(sel)), sel)

    _, final = lax.while_loop(
        lambda state: jnp.any(state[0]), _step, (_pred(carry), carry)
    )
    note_lowered("while")
    return tuple(final[:n_vars])


def _broadcast_lead(c, n: int) -> jnp.ndarray:
    """Give every carry the row axis: arrays already leading with the
    block's row count pass through; sub-lead carries (a shared initial
    accumulator, an invariant capture) replicate per row."""
    c = jnp.asarray(c)
    if c.ndim >= 1 and c.shape[0] == n:
        return c
    return jnp.broadcast_to(c, (n,) + c.shape)
