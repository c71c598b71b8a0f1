"""Executor: compile-once-run-many graph execution.

Replaces the reference's per-task session churn — every Spark task imported
the graph into a fresh native TF Graph+Session and tore it down afterwards
(`DebugRowOps.scala:790`, `TensorFlowOps.scala:76-95`). Here a graph is
lowered once into a jitted XLA executable and cached by
(graph fingerprint, fetches, feed order); `jax.jit` then re-specializes per
concrete block shape, so running B same-shaped blocks costs one compile +
B executions instead of B session setups.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from ..graph.ir import Graph
from ..ops.lowering import build_callable

__all__ = [
    "Executor",
    "FnProgram",
    "ProgramLedger",
    "default_executor",
    "lru_get_or_insert",
    "set_fault_injector",
]


# Fault-injection seam (`tensorframes_tpu.testing.faults`): when
# installed, ``hook(fn, key) -> fn`` wraps every program handed out by
# `Executor.cached` — the one boundary EVERY dispatch crosses (block
# maps, vmapped rows, folds, combines, shard_map programs) — so a
# deterministic chaos harness can fault any dispatch by ordinal /
# device / program / kind without touching verb code. The wrapper is
# applied on the way OUT of the cache (never stored), so the compiled
# program itself is never poisoned. None = production path: one module
# attribute read per cached() call.
_fault_injector = None


def set_fault_injector(hook) -> None:
    global _fault_injector
    _fault_injector = hook


def hand_out(fn: Callable, key: Tuple) -> Callable:
    """``fn`` as a dispatch site is handed it: under the installed fault
    injector, or itself. `Executor.cached` hands out its programs this
    way, and `shape_policy` the exact-shape executables it hangs on one
    (under the entry's key), so they cross the same boundary."""
    return fn if _fault_injector is None else _fault_injector(fn, key)


def lru_get_or_insert(cache, lock, key, make, limit):
    """The ONE locked-LRU discipline both executors use: hit moves to
    the tail; a miss builds OUTSIDE the lock (tracing/compiling can be
    slow) and a lost insert race reuses the winner's value, costing only
    the redundant build. Returns (value, inserted)."""
    with lock:
        fn = cache.get(key)
        if fn is not None:
            cache.move_to_end(key)
            return fn, False
    fn = make()
    with lock:
        winner = cache.get(key)
        if winner is not None:
            cache.move_to_end(key)
            return winner, False
        cache[key] = fn
        while len(cache) > max(1, int(limit)):
            cache.popitem(last=False)
    return fn, True


class FnProgram:
    """What the executor's cache needs of a plain function in a graph's
    place: a fingerprint. It is the function's identity (for a bound
    method, the object's and the method's), which the cached program
    keeps alive, so no other function can come by the same one while the
    entry lives. A lambda written inside the call is a new function each
    time and compiles each time, as any `jax.jit` of it would."""

    def __init__(self, fn: Callable):
        self.fn = fn

    def fingerprint(self) -> str:
        fn = self.fn
        owner = getattr(fn, "__self__", None)
        target = getattr(fn, "__func__", fn)
        name = getattr(target, "__qualname__", type(target).__name__)
        return f"fn:{name}@{id(target):x}" + (
            f"/{id(owner):x}" if owner is not None else ""
        )


class ProgramLedger:
    """What `shape_policy`'s promotion rules read and keep with one
    cached program (``entry.ledger``, `Executor._instrument`): the
    cache ``key``; the program's own ``jitted`` function; ``compile_
    seconds``, what its last XLA compile took (None until one was
    seen), timed whatever the telemetry and cost-ledger switches say,
    so that turning them off does not change what the program does;
    and the policy's own state: ``rungs``, per rung, trailing shapes
    and device the first row count asked for (it runs at its exact
    shape) and whether another has come since, and ``shapes``, per
    exact feed signature of those others the rent its pads have paid
    and what it bought, and ``groups``, per run of equal blocks (its
    rows, the columns, the device) the one pass of the program over the
    run's rows."""

    __slots__ = ("key", "jitted", "compile_seconds", "rungs", "shapes",
                 "groups")

    def __init__(self, key: Tuple, jitted: Callable):
        self.key = key
        self.jitted = jitted
        self.compile_seconds: Optional[float] = None
        self.rungs: Dict[Tuple, List] = {}
        self.shapes: OrderedDict = OrderedDict()
        self.groups: OrderedDict = OrderedDict()


class Executor:
    # Compiled programs from this executor may carry `donate_argnums`
    # (the reduce-combine path): the in-process JAX runtime honors
    # buffer donation. The native host executes lowered modules through
    # its own buffer protocol, so `NativeExecutor` sets this False and
    # verbs build non-donating combines for it.
    supports_donation = True
    # Verbs may route eligible dispatches through the shape-bucketing
    # policy (`shape_policy`) on this executor: jit re-specializes per
    # concrete shape, so quantizing block shapes bounds its compiles.
    supports_bucketing = True
    # The multi-device block scheduler (`runtime.scheduler`) may spread
    # this executor's per-block dispatches across jax.local_devices():
    # programs run wherever their committed inputs live, so placement is
    # a device_put away. The native executor sets this False — it owns
    # its own PJRT host and must never see in-process device_put arrays.
    supports_scheduling = True

    def __init__(self):
        self._cache: "OrderedDict[Tuple, Callable]" = OrderedDict()
        self._lock = threading.Lock()
        self.compile_count = 0  # observability: distinct lowered callables
        # cache observability (surfaced via utils.inspection.executor_stats):
        # a recompile storm shows up as misses growing with call count
        self.cache_hits = 0
        self.cache_misses = 0
        # per-device scheduler ledgers (device label -> count), kept by
        # `runtime.scheduler` under self._lock and surfaced through
        # executor_stats: where dispatches landed and which devices paid
        # jit specializations (compiles are best-effort under
        # concurrent verbs, same caveat as _instrument)
        self.device_dispatches: Dict[str, int] = {}
        self.device_compiles: Dict[str, int] = {}
        # cached-program keys already flagged by the recompile-storm
        # warning (one warning per program, ever)
        self._storm_warned: set = set()

    def cached(
        self,
        kind: str,
        graph: Graph,
        fetches: Sequence[str],
        feed_names: Sequence[str],
        make: Callable[[], Callable],
    ) -> Callable:
        """Generic compile cache: ``kind`` distinguishes execution styles of
        the same graph (plain block call, vmapped per-row, scan fold, ...).
        LRU-bounded (`config.executor_cache_entries`) so a long-lived
        process whose graphs drift does not accumulate compiled
        executables without limit; see `lru_get_or_insert` for the
        locking discipline (the default executor is shared across
        threads)."""
        key = (kind, graph.fingerprint(), tuple(fetches), tuple(feed_names))
        from .. import config as _config

        def timed_make():
            # compile-time attribution (`utils.telemetry`): every cache
            # miss is timed and labeled by graph fingerprint — this is
            # the "trace" phase (lowering + jit wrapping); the real XLA
            # compile per input shape is timed in `_instrument`'s
            # wrapper ("xla" phase)
            from ..utils import telemetry as _tele

            t0 = time.perf_counter()
            fn = self._instrument(key, make())
            t1 = time.perf_counter()
            _tele.record_compile(key[1], kind, t1 - t0, "trace", t0, t1)
            return fn

        fn, inserted = lru_get_or_insert(
            self._cache, self._lock, key,
            timed_make,
            _config.get().executor_cache_entries,
        )
        with self._lock:  # += is not atomic; keep the counts exact
            if inserted:
                self.compile_count += 1
                self.cache_misses += 1
            else:
                self.cache_hits += 1
        return hand_out(fn, key)

    def _instrument(self, key: Tuple, fn: Callable) -> Callable:
        """Wrap a freshly built cached program with per-shape compile
        observability. jit re-specializes (full XLA compile) per distinct
        input shape signature, invisibly to `compile_count` — the
        wrapper watches the jit cache size (`_cache_size`) and logs a
        ONE-TIME recompile-storm warning when a single program crosses
        `config.recompile_warn_shapes` distinct shapes. Programs without
        a `_cache_size` (native-host wrappers, plain callables) pass
        through untouched; the jit cache handle is re-exposed on the
        wrapper so introspection (`jit_shape_compiles`, tests poking
        `fn._cache_size()`) keeps working.

        The wrapper is the cache ENTRY; its ``ledger`` (`ProgramLedger`)
        is what the shape policy's promotion rule reads and keeps, and
        lives and is evicted with the entry."""
        sizer = getattr(fn, "_cache_size", None)
        if not callable(sizer):
            return fn

        # high-water mark of the jit cache size already ATTRIBUTED to a
        # compile event: under concurrent dispatch of one program,
        # several threads can observe the same cache growth (one thread
        # compiles a new shape while another executes a compiled one),
        # and without this gate each would record its own call window as
        # a compile. The first exiting observer of each new size wins —
        # event COUNTS stay exact per specialization; the recorded
        # window is that observer's call, so duration is best-effort
        # under contention.
        compile_seen = [0]
        seen_lock = threading.Lock()
        book = ProgramLedger(key, fn)

        def wrapped(*args, **kwargs):
            from ..utils import telemetry as _tele
            from . import costmodel as _cm

            # jit shape re-specialization attribution: when this call
            # grows the jit cache, the (synchronous) trace+XLA-compile
            # happened inside it — time the call and label the compile
            # event with the program fingerprint. Always tracked (two
            # cache-size reads and two clock reads a dispatch): the
            # seconds are the price of the shape policy's promotion
            # rule; `record_compile` and the ledger gate themselves.
            ledger = _cm.enabled()
            try:
                n0 = sizer()
            except Exception:
                n0 = None
            t0 = time.perf_counter()
            if n0 is not None:
                with seen_lock:
                    if compile_seen[0] < n0:
                        compile_seen[0] = n0  # pre-instrumentation shapes
            out = fn(*args, **kwargs)
            if n0 is not None:
                try:
                    n1 = sizer()
                except Exception:
                    n1 = None
                record = False
                if n1 is not None and n1 > n0:
                    with seen_lock:
                        if n1 > compile_seen[0]:
                            compile_seen[0] = n1
                            record = True
                if record:
                    t1 = time.perf_counter()
                    book.compile_seconds = t1 - t0
                    _tele.record_compile(
                        key[1], key[0], t1 - t0, "xla", t0, t1
                    )
                    if ledger:
                        # the XLA compile for this shape just happened;
                        # lowering again here is tracing + HLO cost
                        # analysis only (no second backend compile) —
                        # the ONE window where modeled cost is captured
                        _cm.capture(key, fn, args)
            if ledger:
                _cm.note_exec(key, args, out)
            from .. import config as _config

            threshold = _config.get().recompile_warn_shapes
            if threshold and key not in self._storm_warned:
                try:
                    n = sizer()
                except Exception:
                    return out
                if n > threshold:
                    with self._lock:
                        if key in self._storm_warned:
                            return out
                        # bounded: programs come and go in a long-lived
                        # service while this set never follows cache
                        # eviction — past the cap an arbitrary entry is
                        # dropped (worst case: an evicted-and-rebuilt
                        # program warns once more)
                        while len(self._storm_warned) >= 1024:
                            self._storm_warned.pop()
                        self._storm_warned.add(key)
                    from ..utils.log import get_logger

                    if _config.get().shape_bucketing:
                        # bucketing is already on: the storm means this
                        # program is not bucketable (non-row-local map /
                        # unclassified reduce) or the ladder itself is
                        # longer than the threshold — don't send the
                        # operator to a knob that is already set
                        remedy = (
                            "this program is not eligible for "
                            "shape_bucketing (non-row-local or "
                            "unclassified graph) or its bucket ladder "
                            "exceeds the threshold; repartition to stable "
                            "block sizes, coarsen shape_bucket_growth, or "
                            "raise recompile_warn_shapes"
                        )
                    else:
                        remedy = (
                            "enable config.shape_bucketing (or "
                            "repartition to stable block sizes) to bound "
                            "XLA compiles"
                        )
                    get_logger("executor").warning(
                        "recompile storm: program %s/%s has compiled %d "
                        "distinct input shapes (> recompile_warn_shapes=%d);"
                        " block shapes are drifting per call — %s",
                        key[0], str(key[1])[:12], n, threshold, remedy,
                    )
            return out

        wrapped._cache_size = sizer
        wrapped.__wrapped__ = fn
        wrapped.ledger = book
        return wrapped

    def program_shape_compiles(self) -> Dict[Tuple, int]:
        """Per-program XLA shape specializations: cache key ``(kind,
        fingerprint, fetches, feeds)`` -> the program's live jit cache
        size. The per-program view behind `jit_shape_compiles` — and
        what `tfs.diagnostics()` renders as the recompile-storm table
        ("which program is eating my startup"). Entries without a jit
        cache handle count as 1."""
        with self._lock:
            items = list(self._cache.items())
        out: Dict[Tuple, int] = {}
        for key, fn in items:
            sizer = getattr(fn, "_cache_size", None)
            if callable(sizer):
                try:
                    out[key] = int(sizer())
                    continue
                except Exception:
                    pass  # a broken sizer reads as 1, never breaks stats
            out[key] = 1
        return out

    def jit_shape_compiles(self) -> int:
        """Total XLA shape specializations across LIVE cached programs:
        the sum of every program's jit cache size (each distinct input
        shape signature = one real compile). This is the recompile-storm
        metric `compile_count` cannot see — under shape bucketing it
        stays O(log max-block-rows) per program no matter how block
        sizes drift. Entries without a jit cache handle count as 1;
        evicted entries' compiles are forgotten with them."""
        return sum(self.program_shape_compiles().values())

    def callable_for(
        self,
        graph: Graph,
        fetches: Sequence[str],
        feed_names: Sequence[str],
    ) -> Callable:
        return self.cached(
            "block",
            graph,
            fetches,
            feed_names,
            lambda: jax.jit(
                build_callable(graph, list(fetches), list(feed_names))
            ),
        )

    def run(
        self,
        graph: Graph,
        fetches: Sequence[str],
        feeds: Dict[str, np.ndarray],
        materialize: bool = False,
    ) -> List[Union["jax.Array", np.ndarray]]:
        """Execute the graph once over ``feeds``.

        Returns DEVICE arrays by default: the call is an async dispatch
        and results stay in device memory, so chained runs pipeline
        without a host round-trip (the reference synced every
        `session.run` to the JVM heap, `DebugRowOps.scala:790-809`).
        Pass ``materialize=True`` to block and copy results to host
        numpy — the explicit opt-in boundary, same contract as
        `Column.host_values`.
        """
        feed_names = sorted(feeds)
        fn = self.callable_for(graph, fetches, feed_names)
        out = fn(*[feeds[n] for n in feed_names])
        if materialize:
            return [np.asarray(o) for o in out]
        return list(out)

    def cache_keys(self) -> List[Tuple]:
        """Snapshot of live compile-cache keys
        ``(kind, graph fingerprint, fetches, feed names)`` — the
        introspection surface the fusion tests use to prove cache
        keying: a fused lazy pipeline
        must create exactly ONE ``"block"``-kind entry (the fused
        fingerprint) where the eager chain creates one per verb."""
        with self._lock:
            return list(self._cache.keys())

    def programs(self) -> List[Callable]:
        """Snapshot of the live cache entries (the programs `cached`
        hands out, before any fault injector)."""
        with self._lock:
            return list(self._cache.values())

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()


_default: Optional[Executor] = None
_native_default: Optional[object] = None
_native_unavailable: Optional[str] = None
_native_lock = threading.Lock()


def _native_default_executor():
    """Lazy process-wide NativeExecutor over the repo CPU plugin, or
    None with the reason recorded. jax_fallback=True is safe HERE
    because the repo CPU plugin claims no shared accelerator device
    (`pjrt_host.cpu_plugin_path` docstring) — mesh kinds on this
    single-device plugin fall back to the in-process JAX executor."""
    global _native_default, _native_unavailable
    # lock-free fast path: after initialization every verb dispatch
    # reads one attribute instead of serializing on the process lock
    if _native_default is not None:
        return _native_default
    if _native_unavailable is not None:
        return None
    with _native_lock:
        if _native_default is not None:
            return _native_default
        if _native_unavailable is not None:
            return None
        try:
            from .native_executor import NativeExecutor
            from .pjrt_host import cpu_plugin_path

            path = cpu_plugin_path()
            if path is None:
                _native_unavailable = (
                    "native/libtfs_pjrt_cpu.so is not built (make -C native)"
                )
                return None
            _native_default = NativeExecutor(path, jax_fallback=True)
            return _native_default
        except Exception as e:  # plugin load/claim failure
            _native_unavailable = f"plugin load failed: {e}"
            return None


def default_executor() -> Executor:
    """The executor verbs use when no ``executor=`` is passed. With
    ``config.native_executor`` = "auto"/"require", single-program kinds
    route through the C++ PJRT host (`NativeExecutor`) — the
    libtensorflow-equivalent spine as the default, not an opt-in."""
    from .. import config as _config

    mode = _config.get().native_executor
    if mode not in ("off", "auto", "require"):
        # fail loud: a typo'd mode silently meaning "off" would defeat
        # exactly the guarantee "require" exists to provide
        raise ValueError(
            f"config.native_executor={mode!r} is not one of "
            "'off' | 'auto' | 'require'"
        )
    if mode in ("auto", "require"):
        ex = _native_default_executor()
        if ex is not None:
            return ex  # type: ignore[return-value]
        if mode == "require":
            raise RuntimeError(
                "config.native_executor='require' but the native host is "
                f"unavailable: {_native_unavailable}"
            )
    global _default
    if _default is None:
        _default = Executor()
    return _default
