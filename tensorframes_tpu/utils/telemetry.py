"""Structured span tracing + metrics: see every block dispatch.

The reference vendored `StepStats`/`NodeExecStats` protos that nothing
ever consumed (SURVEY §5: "tracing: absent"). After the perf PRs made
the hot path device-resident, fused and shape-bucketed, a verb call
fans out into cached programs, bucketed dispatches and async device
folds — a flat counter dict cannot attribute wall time anymore. This
module is the observability layer those protos never had:

- **Spans** — hierarchical timed regions (verb → plan stage → per-block
  dispatch → compile / transfer / execute / host-sync leaves) recorded
  into a bounded thread-safe ring buffer with parent ids and monotonic
  timestamps. Nesting rides contextvars, so a lazy ``.force()``, a
  stream chunk, or a mesh shard_map dispatch attributes to the
  user-facing verb that triggered it. Every span is mirrored into
  `jax.profiler.TraceAnnotation`, so spans line up with the XLA device
  timeline under ``tfs.utils.trace(logdir)``.
- **Metrics registry** — labeled counters (the old flat `stats()` dict
  is a view over the unlabeled ones), gauges (executor cache entries,
  live device buffers, stream queue depth), and fixed-bucket histograms
  (per-verb latency, block rows, compile seconds per program,
  H2D/D2H bytes).
- **Exporters** — `export_chrome_trace(path)` (trace-event JSON,
  loadable in Perfetto / chrome://tracing), `export_prometheus()`
  (Prometheus text format), and `diagnostics()` — a human report that
  merges span aggregates with `executor_stats()` and the
  recompile-storm signal.

Overhead contract: ``config.telemetry`` (env ``TFS_TELEMETRY``, default
ON) gates ALL span recording, histogram observation and annotation —
when off, a span site costs one config read and a no-op context
manager. Counters are always live (they predate this module:
``host_sync``, ``<verb>.calls`` and friends are asserted by tests),
and `record()`/`count()` keep their exact signatures as
thin shims over the registry, so no call site breaks.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

# resolved once: `enabled()` runs at every span site, and a function-local
# import there cost a fifth of the site (config.py imports nothing of the
# package, so there is no cycle to dodge)
from .. import config as _config

__all__ = [
    "Span",
    "enabled",
    "span",
    "dispatch_span",
    "add_event",
    "record_compile",
    "counter_inc",
    "gauge_set",
    "gauge_register",
    "gauge_register_multi",
    "histogram_observe",
    "spans",
    "span_aggregates",
    "metrics_snapshot",
    "flat_counters",
    "labeled_counters",
    "export_chrome_trace",
    "export_prometheus",
    "diagnostics",
    "diagnostics_data",
    "serve",
    "maybe_serve",
    "shutdown",
    "request_scope",
    "current_request",
    "reset",
    "reset_counters",
]


def enabled() -> bool:
    """Telemetry master switch (``config.telemetry`` / ``TFS_TELEMETRY``)."""
    return _config.get().telemetry


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


@dataclass
class Span:
    """One finished timed region. ``t0``/``t1`` are `time.perf_counter`
    seconds (monotonic, process-local); ``parent_id`` links to the
    enclosing span (None for a root); ``kind`` is the coarse phase the
    aggregators group by: ``verb`` | ``stage`` | ``dispatch`` |
    ``compile`` | ``transfer`` | ``host_sync`` | ``span``. Not frozen:
    a frozen dataclass pays `object.__setattr__` per field, and spans
    are constructed on every dispatch exit."""

    span_id: int
    parent_id: Optional[int]
    name: str
    kind: str
    t0: float
    t1: float
    thread: int
    attrs: Dict[str, object] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class _SpanRing:
    """Bounded thread-safe span store. Evicting the oldest spans (not
    refusing new ones) keeps a long-lived service's freshest window
    exportable; ``dropped`` counts what fell off so exports can say so."""

    def __init__(self, maxlen: int):
        self._lock = threading.Lock()
        self._ring: "deque[Span]" = deque(maxlen=max(1, int(maxlen)))
        self.dropped = 0

    def append(self, s: Span) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(s)

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    @property
    def maxlen(self) -> int:
        return self._ring.maxlen or 0


def _ring_size() -> int:
    return int(getattr(_config.get(), "telemetry_ring_entries", 8192))


_ids = itertools.count(1)  # next() is GIL-atomic in CPython
_ring = _SpanRing(8192)

_CURRENT: "contextvars.ContextVar[Optional[int]]" = contextvars.ContextVar(
    "tfs_current_span", default=None
)
_PROGRAM: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "tfs_current_program", default=None
)
_VERB: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "tfs_current_verb", default=None
)
# serving request attribution: the HTTP front-end (serving/server.py)
# and the micro-batcher's dispatcher set this around the verbs a
# request triggers, and every verb span under it stamps it as a
# ``request=`` label — diagnostics and Chrome traces then attribute
# work per request (a coalesced batch carries the joined ids)
_REQUEST: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "tfs_current_request", default=None
)

_annotation_cls = None  # resolved once; False = unavailable


def _annotation(name: str):
    """`jax.profiler.TraceAnnotation` mirror (cheap when no profiler
    trace is active) — or None when jax is unimportable."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            import jax

            _annotation_cls = jax.profiler.TraceAnnotation
        except Exception:
            _annotation_cls = False
    if _annotation_cls is False:
        return None
    try:
        return _annotation_cls(name)
    except Exception:
        return None


class _NullCtx:
    """The disabled-telemetry context: one shared instance, no state."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()


class _SpanCtx:
    """Class-based span context (contextlib generators cost ~10µs per
    enter/exit pair — too much for a per-block dispatch site; this is
    ~3x cheaper). On exit the finished `Span` goes into the ring; an
    exception passing through records ``attrs['error']`` with the
    exception type so a trace of a failed run shows where it died."""

    __slots__ = (
        "name", "kind", "attrs", "sid", "parent", "tok", "ann", "t0",
        "t1", "ptok", "program", "vtok",
    )

    def __init__(self, name, kind, attrs, program=None):
        self.name = name
        self.kind = kind
        self.attrs = attrs
        self.program = program  # non-None => set the program contextvar
        self.ptok = None
        self.vtok = None

    def __enter__(self):
        self.sid = next(_ids)
        self.parent = _CURRENT.get()
        self.tok = _CURRENT.set(self.sid)
        if self.program is not None:
            self.ptok = _PROGRAM.set(self.program)
        if self.kind == "verb":
            # the verb contextvar: what the cost ledger attributes
            # per-verb footprint high-water marks to
            self.vtok = _VERB.set(self.name)
            rid = _REQUEST.get()
            if rid is not None:
                self.attrs["request"] = rid
        ann = _annotation(self.name)
        self.ann = ann
        if ann is not None:
            ann.__enter__()
        self.t0 = time.perf_counter()
        return self.sid

    @property
    def seconds(self) -> float:
        """Duration on the SPAN's clock, valid after exit — the one
        timing source `utils.profiling.record` re-uses for its
        counters and the `verb_seconds` histogram, so a verb's span
        and its histogram observation can never disagree."""
        return self.t1 - self.t0

    def __exit__(self, et, ev, tb):
        t1 = self.t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        if self.ptok is not None:
            _PROGRAM.reset(self.ptok)
        if self.vtok is not None:
            _VERB.reset(self.vtok)
        _CURRENT.reset(self.tok)
        attrs = self.attrs
        if et is not None:
            attrs = dict(attrs)
            attrs["error"] = et.__name__
        _ring.append(
            Span(
                self.sid, self.parent, self.name, self.kind, self.t0, t1,
                threading.get_ident(), attrs,
            )
        )
        return False


def span(name: str, kind: str = "span", **attrs):
    """Record a timed region into the ring (no-op context when telemetry
    is disabled). Entering yields the span id."""
    if not enabled():
        return _NULL
    return _SpanCtx(name, kind, attrs)


def dispatch_span(
    name: str,
    program: Optional[str] = None,
    block: Optional[int] = None,
    rows: Optional[int] = None,
    **attrs,
):
    """A per-block dispatch leaf: a ``dispatch`` span labeled with the
    program fingerprint (what `diagnostics` groups execute time by),
    plus a `block_rows` histogram observation. Sets the current-program
    contextvar so a host-sync triggered inside attributes to the same
    program."""
    if not enabled():
        return _NULL
    if rows is not None:
        histogram_observe("block_rows", float(rows))
    attrs["program"] = program
    attrs["block"] = block
    attrs["rows"] = rows
    return _SpanCtx(name, "dispatch", attrs, program=program)


def current_program() -> Optional[str]:
    """Program fingerprint of the enclosing dispatch span, if any."""
    return _PROGRAM.get()


def current_verb() -> Optional[str]:
    """Name of the enclosing ``verb`` span, if any (the cost ledger's
    per-verb attribution key)."""
    return _VERB.get()


def current_request() -> Optional[str]:
    """Request id of the enclosing `request_scope`, if any."""
    return _REQUEST.get()


class _RequestScope:
    """Context manager setting the ambient request id (serving request
    attribution — see the ``_REQUEST`` contextvar). Class-based like
    `_SpanCtx`: this wraps every served request."""

    __slots__ = ("rid", "tok")

    def __init__(self, rid: str):
        self.rid = rid

    def __enter__(self):
        self.tok = _REQUEST.set(self.rid)
        return self.rid

    def __exit__(self, et, ev, tb):
        _REQUEST.reset(self.tok)
        return False


def request_scope(request_id: str):
    """Label every verb span started inside with ``request=<id>`` —
    the serving front-end's per-request span attribution hook."""
    return _RequestScope(str(request_id))


def current_span_id() -> Optional[int]:
    """Id of the enclosing span, if any — what cross-thread emitters
    (ingest pipeline stages) capture on the consumer thread and pass as
    ``add_event(parent_id=...)`` so worker-thread spans parent to the
    verb that owns them instead of floating as orphan roots."""
    return _CURRENT.get()


def allocate_span_id() -> int:
    """Reserve a span id BEFORE its region is recorded: cross-thread
    emitters (the ingest pipeline) hand the id to worker threads as
    their explicit parent, then record the parent region itself via
    `add_event(span_id=...)` when it closes — children never reference
    an id that will not appear in the export."""
    return next(_ids)


def add_event(
    name: str,
    kind: str,
    t0: float,
    t1: float,
    parent_id: Optional[int] = None,
    span_id: Optional[int] = None,
    **attrs,
) -> None:
    """Record an ALREADY-TIMED region retroactively (parented to the
    current span, or to an explicit ``parent_id`` — the cross-thread
    case, where contextvars do not flow). Used where the region is only
    recognized after the fact — e.g. a jit call that turned out to
    include an XLA shape specialization, or a pipeline stage running on
    a worker thread. ``span_id`` records under a previously
    `allocate_span_id`-reserved id."""
    if not enabled():
        return
    _ring.append(
        Span(
            span_id if span_id is not None else next(_ids),
            parent_id if parent_id is not None else _CURRENT.get(),
            name, kind, t0, t1,
            threading.get_ident(), attrs,
        )
    )


def record_compile(
    program: str,
    cache_kind: str,
    seconds: float,
    phase: str,
    t0: Optional[float] = None,
    t1: Optional[float] = None,
) -> None:
    """Compile-time attribution: one call per timed compile event.
    ``phase`` distinguishes ``trace`` (an `lru_get_or_insert` miss:
    graph lowering + jit wrapping), ``xla`` (a jit shape
    re-specialization — the REAL XLA compile) and ``native`` (a PJRT
    host compile). Fully gated on the master switch — the
    (program, phase)-labeled histogram entries would otherwise
    accumulate per distinct fingerprint in a service that explicitly
    disabled telemetry, and the ``telemetry.compiles.*`` counters would
    leak into the legacy `stats()` dict."""
    if not enabled():
        return
    prog = str(program)
    histogram_observe("compile_seconds", seconds, program=prog, phase=phase)
    counter_inc(f"telemetry.compiles.{phase}")
    if t0 is not None and t1 is not None:
        add_event(
            f"compile[{phase}]:{cache_kind}",
            "compile",
            t0,
            t1,
            program=prog,
            cache_kind=cache_kind,
            phase=phase,
        )


def spans() -> List[Span]:
    """Snapshot of the span ring (oldest first)."""
    return _ring.snapshot()


def spans_dropped() -> int:
    return _ring.dropped


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

LabelItems = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, object]) -> LabelItems:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


# fixed bucket ladders per histogram family — fixed (not adaptive) so
# concurrent observers never re-bucket and exports are stable. These
# defaults are part of the exposition contract (tests pin them);
# operators re-shape a ladder via ``config.histogram_buckets`` /
# TFS_HISTOGRAM_BUCKETS instead of editing this table.
_DEFAULT_BUCKETS: Dict[str, Tuple[float, ...]] = {
    "seconds": (
        1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
        1e-1, 2.5e-1, 5e-1, 1.0, 2.5, 5.0, 10.0, 30.0,
    ),
    "rows": (
        1.0, 8.0, 64.0, 512.0, 4096.0, 32768.0, 262144.0, 2097152.0,
        16777216.0, 134217728.0, 1073741824.0,
    ),
    "bytes": (
        256.0, 4096.0, 65536.0, 1048576.0, 16777216.0, 268435456.0,
        4294967296.0,
    ),
    # 0..1 ratios (bucket fill fractions): resolution concentrated near
    # full, where the ladder autotuner's decisions live
    "fraction": (
        0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 0.95, 1.0,
    ),
}

# histogram name -> bucket family
_HISTOGRAM_FAMILIES: Dict[str, str] = {
    "verb_seconds": "seconds",
    "compile_seconds": "seconds",
    "block_rows": "rows",
    "h2d_bytes": "bytes",
    "d2h_bytes": "bytes",
    "bucket_fill": "fraction",
    # serving batch economics: row/request counts were previously
    # bucketed on the implicit "seconds" ladder (topping out at 30),
    # which parked every real observation in the +Inf overflow bucket
    # and made their quantiles unreadable
    "serve_batch_rows": "rows",
    "serve_batch_fill": "rows",
    "checkpoint_write_seconds": "seconds",
    "incident_capture_seconds": "seconds",
}


def _buckets_for(name: str) -> Tuple[float, ...]:
    """Bucket boundaries for a histogram about to be created: the
    ``config.histogram_buckets`` override (exact metric name wins over
    its bucket family), validated ascending, else the built-in family
    default. A malformed override silently falls back — a bad config
    value must never turn an observation into an exception."""
    fam = _HISTOGRAM_FAMILIES.get(name, "seconds")
    try:
        over = getattr(_config.get(), "histogram_buckets", None)
        if over:
            raw = over.get(name, over.get(fam))
            if raw:
                b = tuple(float(x) for x in raw)
                if b and all(x < y for x, y in zip(b, b[1:])):
                    return b
    except Exception:
        pass  # malformed override falls back to the family default
    return _DEFAULT_BUCKETS[fam]


class _Histogram:
    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets: Tuple[float, ...]):
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        i = 0
        for b in self.buckets:
            if v <= b:
                break
            i += 1
        self.counts[i] += 1
        self.sum += v
        self.count += 1


class MetricsRegistry:
    """Thread-safe labeled counters, gauges and fixed-bucket histograms.

    One lock; every mutation is a few dict ops under it (the same cost
    profile as the `ExecStats` dict this replaces). Gauges come in two
    flavors: *registered* callables (evaluated at export — e.g. executor
    cache entries) and *set* values (pushed by the producer — e.g.
    stream queue depth)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, LabelItems], float] = {}
        self._gauges: Dict[Tuple[str, LabelItems], float] = {}
        self._gauge_fns: Dict[str, Callable[[], float]] = {}
        # name -> (label key, fn returning {label value: gauge value}):
        # one registered callable fanning out to a labeled gauge family
        # (per-device memory gauges), evaluated only at export
        self._gauge_multi_fns: Dict[
            str, Tuple[str, Callable[[], Dict[str, float]]]
        ] = {}
        self._histograms: Dict[Tuple[str, LabelItems], _Histogram] = {}

    # -- counters -------------------------------------------------------
    def counter_inc(
        self, name: str, value: float = 1.0, **labels
    ) -> None:
        key = (name, _label_key(labels))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def flat_counters(self) -> Dict[str, float]:
        """The legacy `stats()` view: unlabeled counters by bare name,
        labeled ones rendered ``name{k=v,...}``."""
        with self._lock:
            items = list(self._counters.items())
        out: Dict[str, float] = {}
        for (name, labels), v in items:
            if not labels:
                out[name] = v
            else:
                lab = ",".join(f"{k}={val}" for k, val in labels)
                out[f"{name}{{{lab}}}"] = v
        return out

    # -- gauges ---------------------------------------------------------
    def gauge_set(self, name: str, value: float, **labels) -> None:
        with self._lock:
            self._gauges[(name, _label_key(labels))] = float(value)

    def gauge_register(self, name: str, fn: Callable[[], float]) -> None:
        """Registered gauges survive `reset()` (they read live process
        state, they don't accumulate)."""
        with self._lock:
            self._gauge_fns[name] = fn

    def gauge_register_multi(
        self, name: str, label: str, fn: Callable[[], Dict[str, float]]
    ) -> None:
        """A registered gauge FAMILY: ``fn()`` returns {label value:
        gauge value} and exports as ``name{label="..."}`` rows. Like
        plain registered gauges, survives `reset()`."""
        with self._lock:
            self._gauge_multi_fns[name] = (label, fn)

    def gauge_values(self) -> Dict[Tuple[str, LabelItems], float]:
        with self._lock:
            out = dict(self._gauges)
            fns = list(self._gauge_fns.items())
            multi = list(self._gauge_multi_fns.items())
        for name, fn in fns:
            try:
                out[(name, ())] = float(fn())
            except Exception:
                pass  # a dead gauge must never break an export
        for name, (label, fn) in multi:
            try:
                for lv, v in fn().items():
                    out[(name, ((label, str(lv)),))] = float(v)
            except Exception:
                pass  # a dead gauge family must never break an export
        return out

    # -- histograms -----------------------------------------------------
    def histogram_observe(self, name: str, value: float, **labels) -> None:
        key = (name, _label_key(labels))
        with self._lock:
            h = self._histograms.get(key)
            if h is None:
                h = _Histogram(_buckets_for(name))
                self._histograms[key] = h
            h.observe(float(value))

    def histogram_snapshot(self):
        with self._lock:
            return {
                key: (h.buckets, tuple(h.counts), h.sum, h.count)
                for key, h in self._histograms.items()
            }

    # -- lifecycle ------------------------------------------------------
    def reset_counters(self) -> None:
        with self._lock:
            self._counters.clear()

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()
            # _gauge_fns survive: they read live state, not history


_registry = MetricsRegistry()


def counter_inc(name: str, value: float = 1.0, **labels) -> None:
    _registry.counter_inc(name, value, **labels)


def gauge_set(name: str, value: float, **labels) -> None:
    _registry.gauge_set(name, value, **labels)


def gauge_register(name: str, fn: Callable[[], float]) -> None:
    _registry.gauge_register(name, fn)


def gauge_register_multi(
    name: str, label: str, fn: Callable[[], Dict[str, float]]
) -> None:
    _registry.gauge_register_multi(name, label, fn)


def histogram_observe(name: str, value: float, **labels) -> None:
    _registry.histogram_observe(name, value, **labels)


def flat_counters() -> Dict[str, float]:
    return _registry.flat_counters()


def labeled_counters() -> Dict[Tuple[str, LabelItems], float]:
    """Structured counter snapshot keyed ``(name, ((label, value),
    ...))`` — what the workload profiler aggregates from (the flat view
    stringifies labels, which cannot be re-keyed reliably)."""
    with _registry._lock:
        return dict(_registry._counters)


def metrics_snapshot():
    """(counters, gauges, histograms) snapshot for exporters/tests."""
    return (
        _registry.flat_counters(),
        _registry.gauge_values(),
        _registry.histogram_snapshot(),
    )


def reset_counters() -> None:
    """The legacy `reset_stats()` semantics: counters only."""
    _registry.reset_counters()


def reset() -> None:
    """Full telemetry reset: spans, counters, gauges, histograms — the
    test-isolation hook (conftest autouse fixture). Registered gauge
    callables survive; the ring is rebuilt at the CURRENT
    ``config.telemetry_ring_entries`` so a scoped override takes effect
    here."""
    global _ring
    _ring = _SpanRing(_ring_size())
    _registry.reset()


# built-in process gauges -----------------------------------------------


def _gauge_executor_cache_entries() -> float:
    """Live compiled-program entries across BOTH process-default
    executors: the in-process JAX executor and the native-host default
    (`config.native_executor="auto"/"require"` routes verbs there, and
    reporting only `_default` would show 0 while the native cache is
    full). Reads module globals only — never constructs an executor."""
    from ..runtime import executor as _exmod

    total = 0.0
    for ex in (_exmod._default, _exmod._native_default):
        if ex is not None:
            total += len(getattr(ex, "_cache", ()))
    return total


def _gauge_live_device_buffers() -> float:
    import jax

    return float(len(jax.live_arrays()))


gauge_register("executor_cache_entries", _gauge_executor_cache_entries)
gauge_register("live_device_buffers", _gauge_live_device_buffers)
# ring overflow was previously visible only inside explain_analyze
# warnings and the Chrome-trace otherData blob; scrapes need it live
gauge_register("spans_dropped", lambda: float(spans_dropped()))


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------


def _union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Total length of the union of [t0, t1] intervals (overlap-safe —
    concurrent verbs on several threads must not count twice)."""
    if not intervals:
        return 0.0
    intervals = sorted(intervals)
    total = 0.0
    cur0, cur1 = intervals[0]
    for a, b in intervals[1:]:
        if a > cur1:
            total += cur1 - cur0
            cur0, cur1 = a, b
        else:
            cur1 = max(cur1, b)
    return total + (cur1 - cur0)


def span_aggregates(span_list: Optional[List[Span]] = None) -> Dict:
    """Structured aggregates over the span ring: wall-clock coverage by
    root spans, totals by verb / by kind / by name (with self time), and
    the per-program compile-vs-execute-vs-host-sync attribution table.
    A span whose parent has left the list counts as a root."""
    ss = spans() if span_list is None else span_list
    if not ss:
        return {
            "window": 0.0, "covered": 0.0, "coverage": 0.0, "roots": 0,
            "spans": 0, "dropped": spans_dropped(),
            "by_verb": {}, "by_kind": {}, "by_name": {}, "by_program": {},
            "by_device": {},
        }
    window0 = min(s.t0 for s in ss)
    window1 = max(s.t1 for s in ss)
    by_id = {s.span_id: s for s in ss}
    roots = [s for s in ss if s.parent_id not in by_id]
    covered = _union_seconds([(s.t0, s.t1) for s in roots])
    window = max(window1 - window0, 1e-12)
    by_verb: Dict[str, Dict[str, float]] = {}
    by_kind: Dict[str, Dict[str, float]] = {}
    by_program: Dict[str, Dict[str, float]] = {}
    dev_intervals: Dict[str, List[Tuple[float, float]]] = {}
    dev_rows: Dict[str, float] = {}
    # self time: a span's duration less the union of its direct
    # children's intervals (clipped to it: a cross-thread child may
    # outlast the region that owns it)
    child_intervals: Dict[int, List[Tuple[float, float]]] = {}
    for s in ss:
        p = by_id.get(s.parent_id)
        if p is not None:
            a, b = max(s.t0, p.t0), min(s.t1, p.t1)
            if b > a:
                child_intervals.setdefault(p.span_id, []).append((a, b))
    by_name: Dict[str, Dict[str, float]] = {}
    for s in ss:
        n = by_name.setdefault(
            s.name, {"count": 0, "seconds": 0.0, "self_seconds": 0.0}
        )
        n["count"] += 1
        n["seconds"] += s.seconds
        n["self_seconds"] += s.seconds - _union_seconds(
            child_intervals.get(s.span_id, [])
        )
        k = by_kind.setdefault(s.kind, {"seconds": 0.0, "count": 0})
        k["seconds"] += s.seconds
        k["count"] += 1
        if s.kind == "verb":
            v = by_verb.setdefault(
                s.name, {"seconds": 0.0, "calls": 0, "rows": 0.0}
            )
            v["seconds"] += s.seconds
            v["calls"] += 1
            v["rows"] += float(s.attrs.get("rows") or 0)
        prog = s.attrs.get("program")
        if prog:
            p = by_program.setdefault(
                str(prog),
                {
                    "compile_s": 0.0, "compiles": 0,
                    "execute_s": 0.0, "dispatches": 0,
                    "host_sync_s": 0.0, "host_syncs": 0,
                },
            )
            if s.kind == "compile":
                p["compile_s"] += s.seconds
                p["compiles"] += 1
            elif s.kind == "dispatch":
                p["execute_s"] += s.seconds
                p["dispatches"] += 1
            elif s.kind == "host_sync":
                p["host_sync_s"] += s.seconds
                p["host_syncs"] += 1
        if s.kind == "dispatch":
            dev = s.attrs.get("device")
            if dev:
                # per-device issue ledger (block-scheduler labels):
                # dispatch spans measure async ISSUE windows, so the
                # union is the time the host spent dispatching to this
                # device, NOT the device's occupancy (a chip that waits
                # for the host 99% of the time reads high here); with
                # the rows, the skew signal across devices
                dev_intervals.setdefault(str(dev), []).append((s.t0, s.t1))
                dev_rows[str(dev)] = dev_rows.get(str(dev), 0.0) + float(
                    s.attrs.get("rows") or 0
                )
    by_device = {
        d: {
            "issue_s": _union_seconds(iv),
            "dispatches": len(iv),
            "rows": dev_rows[d],
        }
        for d, iv in dev_intervals.items()
    }
    return {
        "window": window,
        "covered": covered,
        "coverage": min(1.0, covered / window),
        "roots": len(roots),
        "spans": len(ss),
        "dropped": spans_dropped(),
        "by_verb": by_verb,
        "by_kind": by_kind,
        "by_name": by_name,
        "by_program": by_program,
        "by_device": by_device,
    }


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _json_safe(v):
    """Span attrs carry numpy scalars (row counts come from offset
    arrays); coerce to native JSON types so the export never raises."""
    if isinstance(v, (str, bool, int, float)) or v is None:
        return v
    item = getattr(v, "item", None)
    if callable(item):
        try:
            return item()
        except Exception:
            pass  # non-scalar .item(): fall through to str()
    return str(v)


def export_chrome_trace(path: Optional[str] = None) -> Dict:
    """Span ring as Chrome trace-event JSON (complete "X" events;
    open `chrome://tracing` or https://ui.perfetto.dev and load the
    file). Nesting renders from same-tid timestamp containment, and each
    event's ``args`` carries the span/parent ids, so verb → dispatch →
    compile structure survives the export. Returns the trace object;
    writes it to ``path`` when given."""
    events = []
    for s in spans():
        args = {
            k: _json_safe(v) for k, v in s.attrs.items() if v is not None
        }
        args["span_id"] = s.span_id
        if s.parent_id is not None:
            args["parent_id"] = s.parent_id
        events.append(
            {
                "name": s.name,
                "cat": s.kind,
                "ph": "X",
                "ts": s.t0 * 1e6,  # microseconds, monotonic clock
                "dur": (s.t1 - s.t0) * 1e6,
                "pid": os.getpid(),
                "tid": s.thread,
                "args": args,
            }
        )
    obj = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "tensorframes_tpu.telemetry",
            "spans_dropped": spans_dropped(),
        },
    }
    if path is not None:
        # atomic commit: a scrape or incident capture racing a plain
        # open(path, "w") would read torn JSON mid-dump
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(obj, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    return obj


def _prom_name(name: str) -> str:
    safe = "".join(c if c.isalnum() or c == "_" else "_" for c in name)
    return f"tfs_{safe}"


def _prom_escape(v: str) -> str:
    """Label-value escaping per the exposition format: backslash,
    double-quote and newline MUST be escaped or a value like a shard
    path (``tfs_shard_path`` labels carry arbitrary filesystem paths)
    silently corrupts the whole scrape."""
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _prom_labels(labels: LabelItems, extra: str = "") -> str:
    parts = [f'{k}="{_prom_escape(v)}"' for k, v in labels]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


# HELP text per metric family (exposition format: HELP escapes only
# backslash and newline). Families without an entry get a generic line
# — an absent # HELP is a lint error in several Prometheus toolchains.
_PROM_HELP: Dict[str, str] = {
    "host_sync": "Device-to-host synchronization points",
    "bindings.bytes_placed": (
        "Bytes of bound leaves copied to a device (from the host or from "
        "another device) by verb calls"
    ),
    "bindings.leaves": "Leaves of bound pytrees handed to verb programs",
    "lm.tokens": "Tokens scored by models.lm.score",
    "moe.routed_rows": (
        "Token rows routed to experts (tokens x experts per token x "
        "expert layers) by models.lm.score"
    ),
    "moe.held_rows_expected": (
        "Routed rows expected for the experts held here (routed rows x "
        "held experts / experts) by models.lm.score"
    ),
    "lm.attention_pairs": (
        "Causal query-key pairs (x heads x attention layers) attended by "
        "models.lm.score"
    ),
    "lm.swa_pairs": (
        "Query-key pairs inside the window (x heads x sliding attention "
        "layers) attended by models.lm.score"
    ),
    "lm.swa_blocks": (
        "Query block x key block pairs (x heads x sliding attention layers) "
        "the banded attention grid computed in models.lm.score"
    ),
    "lm.ssm_steps": (
        "State-space scan steps (tokens x state-space layers) of "
        "models.lm.score"
    ),
    "lm.dsa_selected_pairs": (
        "Selected query-key pairs (x heads x sparse attention layers) "
        "attended by models.lm.score"
    ),
    "lm.dsa_index_pairs": (
        "Causal query-key pairs (x index heads x full indexer layers) "
        "scored by the lightning indexer in models.lm.score"
    ),
    "lm.index_reuses": (
        "Layers (x rows) that reused the selection of an earlier indexer "
        "in models.lm.score"
    ),
    "lm.index_threshold_blocks": (
        "Query blocks (x full indexer layers x rows) whose top-k the indexer "
        "chose by an exact threshold in models.lm.score"
    ),
    "lm.index_prefix_blocks": (
        "Query blocks (x full indexer layers x rows) below the indexer's top-k "
        "that kept every causal key in models.lm.score"
    ),
    "lm.hc_stream_bytes": (
        "Bytes of the hyper-connection streams (streams x d x 4 B x tokens x "
        "2 sublayers x layers) of models.lm.score"
    ),
    "lm.head_kernel_tokens": (
        "Tokens whose next-token log-probability the fused head kernel "
        "computed in models.lm.score"
    ),
    "fault_retries": "Classified dispatch retries by fault class",
    "device_evictions": "Failover circuit-breaker device evictions",
    "block_splits": "OOM-triggered block split-retries by verb",
    "device_grant_timeouts": "Device acquisitions abandoned by watchdog",
    "deadline_exceeded": "Verb deadline expiries by verb",
    "verbs_shed": "Verbs rejected by admission control",
    "checkpoint_commits": "Durable-stream checkpoint commits",
    "checkpoint_resumes": "Streams resumed from a durable checkpoint",
    "checkpoint_chunks_skipped": (
        "Committed chunks skipped (never re-decoded) by resumed streams"
    ),
    "checkpoint_write_seconds": "Durable-stream checkpoint commit latency",
    "autotune_adjustments": "Knob adjustments applied by the autotuner",
    "global_dispatches": "Single-program SPMD dispatches by verb",
    "global_collectives": "In-program all-reduces lowered by global reduces",
    "global_pad_rows": "Synthetic rows padded onto sharded lead dims",
    "global_fallbacks": (
        "Dispatches that left the global SPMD path, by reason"
    ),
    "global_stream_folds": (
        "Eager double-buffer folds on global streaming reduces"
    ),
    "row_vectorize_lowered": (
        "Control-flow nodes lowered to masked dense programs, by kind"
    ),
    "row_vectorize_fallbacks": (
        "Graphs kept off the vectorized control-flow path, by reason"
    ),
    "materialize_hits": "Materialization-cache hits served without compute",
    "materialize_misses": "Materialization-cache lookups that missed",
    "materialize_evictions": "Materialization-cache entries evicted (LRU)",
    "materialize_bytes": "Bytes held by the materialization cache",
    "admission_wait_seconds": "Time spent queued for a verb slot",
    "admission_queue_depth": "Verbs queued for admission right now",
    "admission_in_flight": "Admitted top-level verbs in flight",
    "oom_forensics": "Forensic snapshots captured for resource faults",
    "executor_cache_entries": "Live compiled-program cache entries",
    "live_device_buffers": "Live jax arrays across all devices",
    "live_buffer_bytes": "Live jax buffer bytes committed per device",
    "device_bytes_in_use": "Backend memory_stats bytes_in_use per device",
    "device_peak_bytes": "Backend memory_stats peak_bytes_in_use per device",
    "scheduler_queue_depth": (
        "Planned dispatches a verb call never issued per device (0 after "
        "a whole call)"
    ),
    "scheduler.dispatches": "Block-scheduler dispatches issued per device",
    "scheduler.rows": "Rows of the blocks the scheduler issued per device",
    "scheduler.put_seconds": (
        "Host seconds in device_put of scheduled feeds per device"
    ),
    "scheduler.bytes_in": (
        "Bytes of scheduled feeds that changed device or came from the "
        "host, per receiving device"
    ),
    "scheduler.home_plans": (
        "Verb calls whose blocks were all planned on the one device that "
        "holds their columns (a row-local map stays at home)"
    ),
    "scheduler.home_blocks": "Blocks planned by those home plans",
    "scheduler.bytes_back": (
        "Bytes of parts copied to the anchor device before a concat or stack"
    ),
    "scheduler.gather_seconds": (
        "Host seconds in the copies of parts to the anchor device"
    ),
    "stream_queue_depth": "Decoded chunks ready ahead of the consumer",
    "ingest_queue_depth": "Ingest stage input-queue occupancy",
    "ingest_chunks": "Items through each ingest stage",
    "ingest_stage_busy_seconds": "Ingest stage busy time",
    "ingest_stage_wait_seconds": "Ingest stage starved time",
    "verb_seconds": "Verb call latency",
    "compile_seconds": "Compile time by program and phase",
    "serve_requests": "Serving requests accepted per endpoint",
    "serve_batches": "Coalesced serving dispatches per endpoint",
    "serve_shed": "Serving requests shed at a full lane per endpoint",
    "serve_batch_rows": "Rows per coalesced serving dispatch",
    "serve_batch_fill": "Requests coalesced into one serving dispatch",
    "serve_queue_seconds": "Request wait in the batching lane",
    "serve_pending": "Serving requests queued across all lanes",
    "serve_warm_rungs": "Bucket rungs warm-compiled per endpoint",
    "serve_endpoints_registered": "Serving endpoints registered",
    "bucket_fill": "Valid-row fraction of each bucketed dispatch by verb",
    "costmodel_residual": (
        "Span-achieved vs cost-model-predicted time ratio per program"
    ),
    "block_rows": "Rows per block dispatch",
    "h2d_bytes": "Host-to-device transfer bytes",
    "d2h_bytes": "Device-to-host transfer bytes",
    "spans_dropped": "Spans evicted from the trace ring by overflow",
    "incidents_captured": "Incident bundles written by trigger class",
    "incidents_suppressed": (
        "Incident captures suppressed by reason (rate_limit/store/error)"
    ),
    "incident_bytes": "Bytes held by on-disk incident bundles",
    "incident_capture_seconds": "Incident bundle capture latency",
    "plan_rewrites": "Cost-accepted plan-optimizer rewrites by rule",
    "plan_fallbacks": (
        "Relational plan nodes that left the global SPMD path, by reason"
    ),
    "plan_pushdown_rows_skipped": (
        "Rows never decoded thanks to predicate pushdown into the scan"
    ),
    "ingest_rows_decoded": "Rows decoded at the arrow ingest boundary",
}


def _prom_help_text(raw_name: str) -> str:
    text = _PROM_HELP.get(raw_name, f"tensorframes_tpu metric {raw_name}")
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def export_prometheus() -> str:
    """Counters, gauges and histograms in Prometheus text exposition
    format (histograms with cumulative ``le`` buckets + ``_sum`` /
    ``_count``), with ``# HELP`` + ``# TYPE`` headers and escaped label
    values, ready for a textfile collector or the /metrics handler."""
    lines: List[str] = []
    with _registry._lock:
        counters = list(_registry._counters.items())
        hists = [
            (key, (h.buckets, tuple(h.counts), h.sum, h.count))
            for key, h in _registry._histograms.items()
        ]
    gauges = _registry.gauge_values()

    seen_types: set = set()

    def _type(name: str, t: str, raw: str) -> None:
        if name not in seen_types:
            seen_types.add(name)
            lines.append(f"# HELP {name} {_prom_help_text(raw)}")
            lines.append(f"# TYPE {name} {t}")

    for (name, labels), v in sorted(counters):
        pn = _prom_name(name)
        _type(pn, "counter", name)
        lines.append(f"{pn}{_prom_labels(labels)} {v:g}")
    for (name, labels), v in sorted(gauges.items()):
        pn = _prom_name(name)
        _type(pn, "gauge", name)
        lines.append(f"{pn}{_prom_labels(labels)} {v:g}")
    for (name, labels), (buckets, counts, hsum, hcount) in sorted(hists):
        pn = _prom_name(name)
        _type(pn, "histogram", name)
        cum = 0
        for b, c in zip(buckets, counts[:-1]):
            cum += c
            le = 'le="%g"' % b
            lines.append(f"{pn}_bucket{_prom_labels(labels, le)} {cum}")
        cum += counts[-1]
        inf = 'le="+Inf"'
        lines.append(f"{pn}_bucket{_prom_labels(labels, inf)} {cum}")
        lines.append(f"{pn}_sum{_prom_labels(labels)} {hsum:g}")
        lines.append(f"{pn}_count{_prom_labels(labels)} {hcount}")
    return "\n".join(lines) + "\n"


def _fmt_bytes(n) -> str:
    if n is None:
        return "?"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}TiB"  # pragma: no cover - loop always returns


def _fmt_rate(v, unit: str) -> str:
    if v is None:
        return "?"
    for prefix, scale in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(v) >= scale:
            return f"{v / scale:.2f} {prefix}{unit}"
    return f"{v:.2f} {unit}"


def _device_lines(by_device: Dict[str, Dict]) -> Dict[str, Dict]:
    """`span_aggregates`' device lines (issue seconds, dispatches and
    rows, from the ring's dispatch spans) beside what the block
    scheduler's books have handed to the counters since the last reset
    (`BlockSchedule.flush`): bytes that arrived on the device and the
    seconds the host spent putting them there."""
    out = {d: dict(v) for d, v in by_device.items()}
    keys = {"scheduler.bytes_in": "bytes_in", "scheduler.put_seconds": "put_s"}
    for (name, labels), v in labeled_counters().items():
        dev = dict(labels).get("device")
        if name in keys and dev is not None:
            line = out.setdefault(
                dev, {"issue_s": 0.0, "dispatches": 0, "rows": 0.0}
            )
            line[keys[name]] = v
    return out


def diagnostics_data(executor=None) -> Dict:
    """The machine-readable diagnostics payload (what
    ``tfs.diagnostics(format="json")`` and the /diagnostics endpoint
    serve): span aggregates, the cost-ledger roofline join, per-verb
    footprint peaks, per-device memory, device health, the fault ledger
    with OOM forensic snapshots, executor stats and the recompile-storm
    signal. Every value is JSON-serializable; sections that fail to
    collect carry an ``error`` string instead of raising."""
    from .inspection import executor_stats

    ss = spans()
    agg = span_aggregates(ss)
    data: Dict = {
        "telemetry_enabled": enabled(),
        "window": {
            k: agg[k]
            for k in ("window", "covered", "coverage", "roots", "spans",
                      "dropped")
        },
        "verbs": agg["by_verb"],
        "phases": agg["by_name"],
        "devices": _device_lines(agg["by_device"]),
        "programs": agg["by_program"],
    }
    # calls the block scheduler kept on their columns' device, and the
    # blocks of those (`runtime.scheduler`: the home plan)
    counters = flat_counters()
    data["scheduler"] = {
        k: int(counters.get("scheduler." + k, 0))
        for k in ("home_plans", "home_blocks")
    }
    # what a model scored: `models.lm.score`'s counters (tokens, routed
    # rows, attended and selected pairs, the residual's stream bytes)
    data["model"] = {
        k: v for k, v in sorted(counters.items()) if k.startswith(("lm.", "moe."))
    }

    # cost ledger x span join ------------------------------------------
    try:
        from ..runtime import costmodel as _cm

        data["cost"] = {
            "enabled": _cm.enabled(),
            "peaks": _cm.device_peaks(),
            "programs": _cm.roofline(agg["by_program"]),
            "verb_peaks": _cm.verb_peaks(),
        }
    except Exception as e:
        data["cost"] = {"error": f"{type(e).__name__}: {e}"}

    # cost-model accuracy: modeled vs span-achieved residuals -----------
    try:
        from ..runtime import costmodel as _cm

        data["accuracy"] = _cm.residuals(ss)
    except Exception as e:
        data["accuracy"] = {"error": f"{type(e).__name__}: {e}"}

    # bucketing pad waste + fill fractions ------------------------------
    try:
        fill: Dict[str, Dict] = {}
        for (name, labels), (
            _b, _c, hsum, hcount,
        ) in _registry.histogram_snapshot().items():
            if name != "bucket_fill" or not hcount:
                continue
            verb = dict(labels).get("verb", "unattributed")
            f = fill.setdefault(verb, {"sum": 0.0, "count": 0})
            f["sum"] += hsum
            f["count"] += hcount
        data["bucketing"] = {
            "padded_dispatches": int(
                counters.get("shape_bucketing.padded_dispatch", 0)
            ),
            "window_dispatches": int(
                counters.get("shape_bucketing.window_dispatch", 0)
            ),
            "pad_rows": int(counters.get("shape_bucketing.pad_rows", 0)),
            # promotion (`shape_policy`): dispatches that ran exact as
            # their rung's first size, rungs a second size made compile
            # their padded program, dispatches served by an exact-shape
            # executable a repeated pad bought, shapes bought, compiles
            # that failed, shapes on a device with no known bandwidth
            # to price a pad with; runs of equal blocks dispatched as
            # one group, and the blocks those covered
            **{
                name: int(counters.get("shape_bucketing." + counter, 0))
                for name, counter in (
                    ("first_size_dispatches", "first_size_dispatch"),
                    ("rungs_widened", "rungs_widened"),
                    ("promoted_dispatches", "promoted_dispatch"),
                    ("promotions", "promotions"),
                    ("promotions_failed", "promotion_failed"),
                    ("promotions_unpriced", "promotion_unpriced"),
                    ("group_dispatches", "group_dispatch"),
                    ("grouped_blocks", "grouped_blocks"),
                )
            },
            "fill": {
                v: {
                    "mean": f["sum"] / f["count"],
                    "dispatches": f["count"],
                }
                for v, f in sorted(fill.items())
            },
        }
    except Exception as e:
        data["bucketing"] = {"error": f"{type(e).__name__}: {e}"}

    # per-device memory -------------------------------------------------
    try:
        from ..runtime import costmodel as _cm

        data["memory"] = _cm.memory_overview()
    except Exception as e:
        data["memory"] = [{"error": f"{type(e).__name__}: {e}"}]

    # fault tolerance: device health + ledger + forensics ---------------
    try:
        from ..runtime import faults as _faults
        from ..runtime.scheduler import device_health

        data["health"] = device_health().table()
        data["faults"] = _faults.ledger_snapshot()
        data["forensics"] = _faults.forensics_snapshot()
    except Exception as e:
        data["faults_error"] = f"{type(e).__name__}: {e}"

    # closed-loop autotuner: tuned knobs, pins, recent decisions --------
    try:
        from ..runtime import autotune as _autotune

        data["autotune"] = _autotune.state()
    except Exception as e:
        data["autotune"] = {"error": f"{type(e).__name__}: {e}"}

    # durable streams: checkpoint/resume accounting ---------------------
    try:
        from ..runtime import checkpoint as _checkpoint

        data["checkpoint"] = _checkpoint.state()
    except Exception as e:
        data["checkpoint"] = {"error": f"{type(e).__name__}: {e}"}

    # global sharded frames: SPMD dispatch accounting --------------------
    try:
        from .. import globalframe as _globalframe

        data["globalframe"] = _globalframe.state()
    except Exception as e:
        data["globalframe"] = {"error": f"{type(e).__name__}: {e}"}

    # row vectorization: masked-dense control-flow accounting ------------
    try:
        from ..graph import vectorize as _vectorize

        data["row_vectorize"] = _vectorize.state()
    except Exception as e:
        data["row_vectorize"] = {"error": f"{type(e).__name__}: {e}"}

    # materialization cache: hit/store/eviction accounting ---------------
    try:
        from ..runtime import materialize as _materialize

        data["materialize"] = _materialize.state()
    except Exception as e:
        data["materialize"] = {"error": f"{type(e).__name__}: {e}"}

    # plan optimizer: relational rewrite/fallback/pushdown accounting ----
    try:
        from ..graph import plan as _planmod

        data["plan_optimizer"] = _planmod.state()
    except Exception as e:
        data["plan_optimizer"] = {"error": f"{type(e).__name__}: {e}"}

    # flight recorder: incident capture/suppression accounting -----------
    try:
        from ..runtime import blackbox as _blackbox

        data["blackbox"] = _blackbox.state()
    except Exception as e:
        data["blackbox"] = {"error": f"{type(e).__name__}: {e}"}

    # executor + recompile-storm signal ---------------------------------
    try:
        es = dict(executor_stats(executor))
        if isinstance(es.get("faults"), dict):
            # data["forensics"] above is the one canonical copy — the
            # executor_stats merge would duplicate every snapshot (each
            # embedding a per-device memory table) in the payload
            es["faults"] = {
                k: v for k, v in es["faults"].items() if k != "forensics"
            }
        data["executor"] = es
        from ..runtime.executor import default_executor

        ex = executor if executor is not None else default_executor()
        per_prog = getattr(ex, "program_shape_compiles", None)
        threshold = _config.get().recompile_warn_shapes
        if callable(per_prog):
            shapes = per_prog()
            data["recompile"] = {
                "threshold": threshold,
                "worst": max(shapes.values()) if shapes else 0,
                "storming": {
                    f"{k[0]}/{str(k[1])[:12]}": n
                    for k, n in shapes.items()
                    if threshold and n > threshold
                },
            }
    except Exception as e:
        data["executor_error"] = f"{type(e).__name__}: {e}"

    data["gauges"] = {
        name + _prom_labels(labels): v
        for (name, labels), v in sorted(_registry.gauge_values().items())
    }
    return data


def _render_diagnostics(data: Dict) -> str:
    lines = ["tensorframes-tpu diagnostics", "=" * 28]
    if not data["telemetry_enabled"]:
        lines.append(
            "telemetry is DISABLED (config.telemetry=False / "
            "TFS_TELEMETRY=0): spans below reflect only what was "
            "recorded while it was on"
        )
    w = data["window"]
    lines.append(
        f"window: {w['window']:.4f}s wall, "
        f"{w['coverage'] * 100:.1f}% attributed to {w['roots']} root "
        f"span(s) ({w['spans']} spans buffered, {w['dropped']} dropped)"
    )

    cost = data.get("cost", {})
    if data["verbs"]:
        lines.append("")
        lines.append("verbs:")
        for name, v in sorted(
            data["verbs"].items(), key=lambda kv: -kv[1]["seconds"]
        ):
            rows = f"  rows={int(v['rows'])}" if v["rows"] else ""
            lines.append(
                f"  {name:<28} calls={v['calls']:<4} "
                f"total={v['seconds']:.4f}s{rows}"
            )
    if data["phases"]:
        lines.append("")
        lines.append("time by phase (span name: count, total, self = total"
                     " less its child spans; dispatch is async issue time,"
                     " not device occupancy):")
        for name, k in sorted(
            data["phases"].items(), key=lambda kv: -kv[1]["self_seconds"]
        ):
            lines.append(
                f"  {name:<28} n={k['count']:<6} "
                f"total={k['seconds']:.6f}s self={k['self_seconds']:.6f}s"
            )
    if data.get("devices"):
        lines.append("")
        lines.append(
            "devices (block-scheduler dispatch labels; issue = union of "
            "the host's dispatch-issue spans, not device occupancy; in = "
            "feeds that changed device or came from the host, put = the "
            "host's seconds in their device_put):"
        )
        for dev, d in sorted(data["devices"].items()):
            lines.append(
                f"  {dev:<10} dispatches={d['dispatches']:<5} "
                f"rows={d['rows']:<10.0f} issue={d['issue_s']:.4f}s "
                f"in={_fmt_bytes(d.get('bytes_in', 0))} "
                f"put={d.get('put_s', 0.0):.4f}s"
            )
        home = data.get("scheduler", {})
        if home.get("home_plans"):
            lines.append(
                f"  home plans: {home['home_plans']} call(s) kept "
                f"{home['home_blocks']} block(s) of a row-local map on "
                "the device that holds their columns"
            )
    if data.get("model"):
        lines.append("")
        lines.append("model (models.lm.score's counters, summed over calls):")
        for name, value in data["model"].items():
            lines.append(f"  {name:<26} {value:,.0f}")
    if data["programs"]:
        lines.append("")
        lines.append("programs (by graph fingerprint):")
        for prog, p in sorted(
            data["programs"].items(),
            key=lambda kv: -(kv[1]["compile_s"] + kv[1]["execute_s"]),
        ):
            lines.append(
                f"  {prog:<16} compile={p['compile_s']:.4f}s "
                f"({p['compiles']}x)  execute={p['execute_s']:.4f}s "
                f"({p['dispatches']} dispatch(es))  "
                f"host_sync={p['host_sync_s']:.4f}s"
            )

    # cost ledger: the roofline join ------------------------------------
    if cost.get("programs"):
        peaks = cost.get("peaks", {})
        kind = peaks.get("device_kind")
        known = peaks.get("matmul_flops_s") or peaks.get("hbm_bytes_s")
        lines.append("")
        lines.append(
            "cost ledger (XLA-modeled, captured at compile; achieved = "
            "modeled total / attributed execute time"
            + (
                f"; peaks for {kind})"
                if known
                else f"; no datasheet peak for {kind!r} — fractions "
                "unknown)"
            )
        )
        for r in cost["programs"]:
            if not r["execs"] and not r["dispatches"]:
                continue
            ffrac = r["flops_frac_of_peak"]
            hfrac = r["hbm_frac_of_peak"]
            frac = ""
            if ffrac is not None or hfrac is not None:
                frac = (
                    f"  peak: flops={ffrac * 100:.1f}%"
                    if ffrac is not None
                    else "  peak: flops=?"
                )
                frac += (
                    f" hbm={hfrac * 100:.1f}%"
                    if hfrac is not None
                    else " hbm=?"
                )
            lines.append(
                f"  {r['program']:<16} execs={r['execs']:<5} "
                f"flops/exec={_fmt_rate(r['flops_per_exec'], 'FLOP')} "
                f"hbm/exec={_fmt_bytes(r['bytes_per_exec'])} "
                f"footprint={_fmt_bytes(r['footprint_bytes'])}"
                + (
                    "" if r["temp_known"] else "(+temp?)"
                )
                + f" achieved={_fmt_rate(r['achieved_flops_s'], 'FLOP/s')}"
                f"/{_fmt_rate(r['achieved_hbm_bytes_s'], 'B/s')}"
                + frac
            )
        if cost.get("verb_peaks"):
            lines.append(
                "verb footprint high-water (largest modeled single "
                "dispatch):"
            )
            for verb, pk in sorted(cost["verb_peaks"].items()):
                lines.append(
                    f"  {verb:<28} {_fmt_bytes(pk['bytes'])} "
                    f"(program {str(pk['program'])[:12]}, "
                    f"rows={pk['rows']})"
                )

    # cost-model accuracy ----------------------------------------------
    acc = data.get("accuracy", {})
    if acc.get("programs"):
        warn = acc.get("warn_ratio")
        fit = acc.get("fit", {})
        lines.append("")
        lines.append(
            "cost-model accuracy (achieved vs predicted per dispatch; "
            "predictions from the process-fitted effective throughput "
            f"{_fmt_rate(fit.get('bytes_per_s'), 'B/s')} / "
            f"{_fmt_rate(fit.get('flops_per_s'), 'FLOP/s')}; "
            f"flag threshold x{warn:g}):"
        )
        for fp, p in sorted(
            acc["programs"].items(),
            key=lambda kv: -(kv[1]["residual_ratio"] or 0.0),
        ):
            ratio = p["residual_ratio"]
            if ratio is None:
                continue
            flag = "  ** MODEL MISPRICES THIS PROGRAM" if p["flagged"] else ""
            lines.append(
                f"  {fp:<16} residual={ratio:.2f}x "
                f"({p['dispatches']} dispatch(es), "
                f"achieved {p['achieved_s']:.4f}s vs predicted "
                f"{p['predicted_s']:.4f}s){flag}"
            )

    # bucketing pad waste ----------------------------------------------
    bk = data.get("bucketing", {})
    if bk.get("padded_dispatches") or bk.get("fill"):
        lines.append("")
        lines.append(
            f"bucketing: {bk.get('padded_dispatches', 0)} padded "
            f"dispatch(es), {bk.get('window_dispatches', 0)} window "
            f"dispatch(es), {bk.get('pad_rows', 0)} pad row(s) "
            "(rows computed beyond the real ones, paid for the bounded "
            f"compile count); {bk.get('first_size_dispatches', 0)} "
            "first-size dispatch(es) at their exact shape, "
            f"{bk.get('rungs_widened', 0)} rung(s) widened by a second "
            f"size; {bk.get('promoted_dispatches', 0)} promoted "
            f"dispatch(es) on {bk.get('promotions', 0)} exact shape(s) "
            f"bought ({bk.get('promotions_failed', 0)} failed, "
            f"{bk.get('promotions_unpriced', 0)} unpriced); "
            f"{bk.get('group_dispatches', 0)} group dispatch(es) over "
            f"{bk.get('grouped_blocks', 0)} equal block(s)"
        )
        for verb, f in bk.get("fill", {}).items():
            lines.append(
                f"  fill[{verb}]: mean={f['mean']:.3f} over "
                f"{f['dispatches']} bucketed dispatch(es)"
            )

    # fault tolerance: device health + the fault ledger -----------------
    if "faults_error" in data:
        lines.append(
            f"fault state unavailable: {data['faults_error']}"
        )
    else:
        health = data.get("health", [])
        ledger = data.get("faults", {})
        lines.append("")
        if health:
            lines.append(
                "device health (failover circuit breaker; closed "
                "circuits are not listed):"
            )
            for row in health:
                lines.append(
                    f"  {row['device']:<10} {row['state']:<9} "
                    f"failures={row['failures']} "
                    f"cooldown={row['cooldown_s']}s "
                    f"retry_in={row['retry_in_s']}s"
                )
        else:
            lines.append("device health: all devices healthy")
        if any(ledger.values()):
            lines.append(
                "faults: "
                + " ".join(f"{k}={v}" for k, v in sorted(ledger.items()))
            )
        for snap in data.get("forensics", []):
            modeled = snap.get("modeled") or {}
            lines.append(
                f"  oom[{snap.get('verb')}] program "
                f"{str(snap.get('program'))[:12]} rows={snap.get('rows')} "
                f"depth={snap.get('depth')} -> {snap.get('decision')}; "
                "modeled footprint "
                f"{_fmt_bytes(modeled.get('footprint_bytes'))}"
            )

    # per-device memory -------------------------------------------------
    mem = [m for m in data.get("memory", []) if "error" not in m]
    if mem:
        lines.append("")
        lines.append(
            "device memory (live jax buffers; bytes_in_use/peak from "
            "backend memory_stats, '?' where unreported):"
        )
        for m in mem:
            lines.append(
                f"  {m['device']:<10} live={_fmt_bytes(m['live_buffer_bytes'])}"
                f" ({m['live_buffers']} buffer(s)) "
                f"in_use={_fmt_bytes(m['bytes_in_use'])} "
                f"peak={_fmt_bytes(m['peak_bytes_in_use'])}"
            )

    # closed-loop autotuner ---------------------------------------------
    at = data.get("autotune", {})
    if at and "error" not in at:
        tuned = at.get("tuned", {})
        ep_windows = at.get("endpoint_windows", {})
        if at.get("enabled") or tuned or ep_windows:
            lines.append("")
            lines.append(
                "autotune: "
                + ("loop ON" if at.get("enabled") else "loop off")
                + (
                    f" (running, {at.get('cycles', 0)} cycle(s), every "
                    f"{at.get('interval_s', 0):g}s)"
                    if at.get("running")
                    else ""
                )
            )
            for knob, v in sorted(tuned.items()):
                lines.append(f"  tuned {knob} = {v}")
            for ep, w in sorted(ep_windows.items()):
                lines.append(
                    f"  tuned serve_batch_window_ms[{ep}] = {w:g}"
                )
            if at.get("pinned"):
                lines.append(
                    "  pinned (never tuned): "
                    + ", ".join(at["pinned"])
                )
            for dec in at.get("decisions", [])[-4:]:
                lines.append(
                    f"  decision: {dec.get('knob')} ({dec.get('scope')}) "
                    f"{dec.get('current')} -> {dec.get('proposed')} "
                    f"[{dec.get('outcome')}]"
                )

    # durable streams: checkpoint/resume accounting ---------------------
    ck = data.get("checkpoint", {})
    if ck and "error" not in ck and (
        ck.get("commits") or ck.get("resumes") or ck.get("ignored")
    ):
        lines.append("")
        lines.append(
            f"durable streams: {ck.get('commits', 0)} commit(s), "
            f"{ck.get('resumes', 0)} resume(s), "
            f"{ck.get('chunks_skipped', 0)} committed chunk(s) skipped"
            + (
                f", {ck['ignored']} checkpoint(s) ignored"
                if ck.get("ignored") else ""
            )
        )
        lc = ck.get("last_commit")
        if lc:
            lines.append(
                f"  last commit: {lc['path']} watermark={lc['watermark']}"
                f" partials={lc['partials']} "
                f"{_fmt_bytes(lc['bytes'])} in "
                f"{lc['write_seconds'] * 1e3:.1f}ms"
            )
        lr = ck.get("last_resume")
        if lr:
            lines.append(
                f"  last resume: {lr['path']} "
                f"watermark={lr['watermark']} partials={lr['partials']}"
            )

    # global sharded frames ----------------------------------------------
    gf = data.get("globalframe", {})
    if gf and "error" not in gf and (
        gf.get("frames") or gf.get("dispatches") or gf.get("fallbacks")
    ):
        lines.append("")
        lines.append(
            f"global frames: {gf.get('frames', 0)} frame(s) over "
            f"{gf.get('shards') or '?'} shard(s), "
            f"{gf.get('dispatches', 0)} SPMD dispatch(es), "
            f"{gf.get('collectives', 0)} in-program collective(s), "
            f"{gf.get('pad_rows', 0)} pad row(s) on sharded lead dims"
        )
        for reason, n in sorted(gf.get("fallbacks", {}).items()):
            lines.append(f"  fallback {reason}: {n} dispatch(es)")
        if gf.get("stream_folds"):
            lines.append(
                f"  streaming double-buffer: {gf['stream_folds']} eager "
                "fold(s) overlapped sharded H2D"
            )

    # row vectorization ---------------------------------------------------
    rv = data.get("row_vectorize", {})
    if rv and "error" not in rv and (
        rv.get("lowered") or rv.get("fallbacks")
    ):
        lines.append("")
        low = rv.get("lowered", {})
        lines.append(
            "row vectorization: "
            f"{low.get('cond', 0)} cond->select and "
            f"{low.get('while', 0)} while->masked-fixed-point "
            "lowering(s)"
        )
        for reason, n in sorted(rv.get("fallbacks", {}).items()):
            lines.append(f"  fallback {reason}: {n} graph(s)")

    # materialization cache ----------------------------------------------
    mat = data.get("materialize", {})
    if mat and "error" not in mat and (
        mat.get("hits") or mat.get("misses") or mat.get("stores")
        or mat.get("entries")
    ):
        lines.append("")
        lines.append(
            f"materialization cache: {mat.get('hits', 0)} hit(s), "
            f"{mat.get('misses', 0)} miss(es), "
            f"{mat.get('stores', 0)} store(s), "
            f"{mat.get('evictions', 0)} eviction(s); "
            f"{mat.get('entries', 0)} entry(ies) holding "
            f"{_fmt_bytes(mat.get('bytes', 0))} of "
            f"{_fmt_bytes(mat.get('budget_bytes', 0))} budget"
        )
        if mat.get("rejected"):
            lines.append(
                f"  {mat['rejected']} store(s) rejected by admission "
                "pricing (modeled recompute cheaper than store+load)"
            )
        if mat.get("drift_refusals") or mat.get("corrupt_dropped"):
            lines.append(
                f"  {mat.get('drift_refusals', 0)} drift refusal(s), "
                f"{mat.get('corrupt_dropped', 0)} corrupt entry(ies) dropped"
            )
        lh = mat.get("last_hit")
        if lh:
            lines.append(
                f"  last hit: program {lh['program']} "
                f"{_fmt_bytes(lh['bytes'])} in "
                f"{lh['load_seconds'] * 1e3:.1f}ms"
            )

    # plan optimizer -----------------------------------------------------
    po = data.get("plan_optimizer", {})
    if po and "error" not in po and (
        po.get("forces") or po.get("optimize_runs")
        or po.get("executed_nodes")
    ):
        lines.append("")
        lines.append(
            f"plan optimizer: {po.get('forces', 0)} plan force(s), "
            f"{po.get('optimize_runs', 0)} optimize run(s), "
            f"{po.get('executed_nodes', 0)} node(s) executed, "
            f"{po.get('cache_hits', 0)} materialization hit(s); "
            f"{po.get('pushdown_rows_skipped', 0)} row(s) never decoded "
            "via predicate pushdown"
        )
        for rule, n in sorted((po.get("rewrites") or {}).items()):
            lines.append(f"  rewrite {rule}: {n} accepted")
        for rule, n in sorted((po.get("rejected") or {}).items()):
            lines.append(f"  rewrite {rule}: {n} cost-rejected")
        for reason, n in sorted((po.get("fallbacks") or {}).items()):
            lines.append(f"  fallback {reason}: {n} node(s)")

    # flight recorder ----------------------------------------------------
    bb = data.get("blackbox", {})
    if bb and "error" not in bb and (
        bb.get("captured") or bb.get("suppressed") or bb.get("bundles")
    ):
        lines.append("")
        lines.append(
            f"flight recorder: {bb.get('captured', 0)} incident(s) "
            f"captured; {bb.get('bundles', 0)} bundle(s) holding "
            f"{_fmt_bytes(bb.get('bytes', 0))} in {bb.get('dir')}"
        )
        for reason, n in sorted((bb.get("suppressed") or {}).items()):
            lines.append(f"  suppressed {reason}: {n} capture(s)")
        last = bb.get("last")
        if last:
            lines.append(
                f"  last: {last.get('id')} trigger={last.get('trigger')} "
                f"class={last.get('fault_class')} "
                f"verb={last.get('verb')} program={last.get('program')}"
            )

    # executor + recompile-storm signal ---------------------------------
    if "executor_error" in data:
        lines.append(
            f"executor stats unavailable: {data['executor_error']}"
        )
    else:
        es = data.get("executor", {})
        lines.append("")
        lines.append(
            "executor: "
            + " ".join(f"{k}={v}" for k, v in sorted(es.items()))
        )
        rc = data.get("recompile")
        if rc is not None:
            if rc["storming"]:
                lines.append(
                    f"recompile storm: {len(rc['storming'])} program(s) "
                    f"over recompile_warn_shapes={rc['threshold']}:"
                )
                for key, n in sorted(
                    rc["storming"].items(), key=lambda kv: -kv[1]
                ):
                    lines.append(f"  {key}: {n} compiled shapes")
            else:
                lines.append(
                    f"recompile storm: none (max {rc['worst']} "
                    f"shape(s)/program, threshold {rc['threshold']})"
                )

    if data["gauges"]:
        lines.append("")
        lines.append("gauges:")
        for name, v in data["gauges"].items():
            lines.append(f"  {name} = {v:g}")
    return "\n".join(lines)


def serve(port: Optional[int] = None, host: Optional[str] = None):
    """Start the live telemetry HTTP endpoint (`utils.telemetry_http`):
    ``/metrics`` (Prometheus text), ``/healthz`` (device-health JSON),
    ``/diagnostics`` (JSON), ``/trace`` (Chrome trace JSON) and
    ``/profile`` (a live workload-profile snapshot) on a daemon
    thread. ``port`` defaults to ``config.telemetry_port``
    (``TFS_TELEMETRY_PORT``); pass ``port=0`` for an ephemeral port.
    Binds ``config.telemetry_host`` (127.0.0.1 by default — the
    endpoint has no auth). Returns the `TelemetryServer` handle
    (``.port`` / ``.url`` / ``.close()``)."""
    from . import telemetry_http as _http

    return _http.serve(port=port, host=host)


def maybe_serve():
    """Import-time auto-start: serve IFF ``config.telemetry_port`` is
    non-zero (i.e. the operator set TFS_TELEMETRY_PORT). Never raises —
    a busy port logs a warning instead of breaking the import."""
    if not getattr(_config.get(), "telemetry_port", 0):
        return None
    try:
        return serve()
    except Exception as e:
        from .log import get_logger

        get_logger("telemetry").warning(
            "telemetry endpoint auto-start failed (TFS_TELEMETRY_PORT/"
            "config.telemetry_port): %s: %s", type(e).__name__, e,
        )
        return None


def shutdown() -> bool:
    """Gracefully stop the process-wide telemetry/serving HTTP endpoint
    (`utils.telemetry_http`): unbinds the port, joins the serve thread.
    Returns True when a server was running, False when this was a no-op.
    Mounted routes (the serving front-end) stay registered — a later
    `serve()` picks them up again."""
    from . import telemetry_http as _http

    return _http.shutdown()


def diagnostics(executor=None, format: str = "text"):
    """The one-call "where did my wall time go" report: span coverage,
    per-verb totals, time by phase, the per-program
    compile/execute/host-sync attribution table (keyed by graph
    fingerprint), the cost-ledger roofline (modeled flops / HBM bytes /
    footprint and achieved-vs-peak fractions per program), per-device
    memory, OOM forensics, merged with `executor_stats()` and the
    recompile-storm signal. ``format="text"`` (default) renders the
    human table; ``format="json"`` returns the machine-readable dict
    (`diagnostics_data`) so tests and CI consume structured data
    instead of scraping text. Exposed as ``tfs.diagnostics()``."""
    if format not in ("text", "json"):
        raise ValueError(
            f"diagnostics format={format!r} is not one of 'text' | 'json'"
        )
    data = diagnostics_data(executor)
    if format == "json":
        return data
    return _render_diagnostics(data)
