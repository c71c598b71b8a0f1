"""NativeExecutor: run verbs through the C++ PJRT host.

Drop-in for `runtime.Executor`: graphs lower to StableHLO once (JAX used
as a tracer only — no JAX backend touches the device), then compile and
EVERY execution (H2D, run, D2H) goes through the native host
(native/pjrt_host.cc). Pass ``executor=NativeExecutor(...)`` to any verb.

All single-program execution kinds run natively: plain block calls,
vmapped per-row programs, `lax.scan` folds, and the chunked-aggregate
stages each lower to ONE StableHLO module, which is exactly what the
host consumes. shard_map MESH kinds run natively too when the host's
plugin exposes enough devices (``NativeExecutor(devices=8)`` with the
repo CPU plugin): the lowered module carries ``mhlo.num_partitions``,
the plugin compiles it SPMD and executes all partitions in parallel,
and the host keeps its global-view calling convention — zero Python,
zero in-process JAX backend in the execution path. On a single-device
plugin (one chip) mesh kinds still need the in-process
JAX backend and remain opt-in via ``jax_fallback``.

This completes the reference-parity story for the native runtime: where
TensorFrames' workers called libtensorflow through JNI per partition for
EVERY verb (`DebugRowOps.scala:790-809`), the verbs here call a C++ PJRT
host that owns the TPU client.
"""

from __future__ import annotations

import re
import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from ..graph.ir import Graph
from ..ops.lowering import build_callable
from .pjrt_host import PjrtHost

__all__ = ["NativeExecutor"]

# shard_map programs span a multi-device mesh; they execute natively
# when the host has enough devices, otherwise they need the in-process
# JAX executor (see `cached`).
_MESH_KIND_PREFIXES = ("shmap-", "shred-", "shfold-", "shagg-")

# Lowering flips the PROCESS-GLOBAL jax_use_shardy_partitioner flag
# (restored in a finally); concurrent first-call compiles from two
# threads would race the flip/restore and could leave the flag off for
# unrelated JAX code. One lock serializes all native lowerings.
_LOWER_LOCK = threading.Lock()


class NativeExecutor:
    """Compile cache + execution via the native PJRT host.

    ``devices``: request a device count from the plugin (the repo CPU
    plugin honors ``cpu_device_count``; required for native mesh
    execution). Note: one host per process per plugin; don't mix with a
    JAX backend that owns the same device in-process.
    """

    def __init__(
        self,
        plugin_path: Optional[str] = None,
        jax_fallback: bool = False,
        devices: Optional[int] = None,
    ):
        create_options = (
            {"cpu_device_count": int(devices)} if devices else None
        )
        self._bind_host(
            PjrtHost(plugin_path, create_options=create_options),
            jax_fallback,
        )

    # The host executes lowered modules through its own buffer protocol;
    # donation aliasing is not part of that contract, so verbs build
    # non-donating combine programs for this executor.
    supports_donation = False
    # Shape bucketing applies here too: `_native_run` compiles one host
    # executable per input shape signature, so quantizing block shapes
    # bounds native compiles exactly as it bounds jit specializations.
    supports_bucketing = True
    # Never block-scheduled: execution flows through the host's own
    # buffer protocol, and an in-process jax.device_put beside a host
    # that may own the same device is the documented double-client
    # hazard. The block scheduler skips this executor (and an explicit
    # devices= on a verb raises).
    supports_scheduling = False

    def _bind_host(self, host, jax_fallback: bool = False) -> None:
        """All non-host state in one place (also the seam tests use to
        wrap an existing host without claiming the plugin twice)."""
        self.host = host
        self._cache: "OrderedDict[Tuple, Callable]" = OrderedDict()
        self._lock = threading.Lock()
        self.compile_count = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._allow_jax_fallback = jax_fallback
        self._jax_fallback = None

    @classmethod
    def for_host(cls, host, jax_fallback: bool = False) -> "NativeExecutor":
        """Executor over an ALREADY-CREATED host (one host per process
        per plugin; creating a second claims the device again)."""
        ex = cls.__new__(cls)
        ex._bind_host(host, jax_fallback)
        return ex

    @staticmethod
    def _ledger_key(label: Optional[Tuple], traceable: Callable) -> Tuple:
        """The cost ledger's (kind, fingerprint) for a native program:
        the executor cache key when `cached` routed here, else the
        function front-end's name (the same fallback labeling
        `record_compile` uses)."""
        if label is not None:
            return label
        return ("fn", getattr(traceable, "__name__", "<fn>"))

    def _native_run(
        self, traceable: Callable, label: Optional[Tuple] = None
    ) -> Callable:
        """Wrap a jittable function (possibly taking/returning pytrees)
        as a native-host call: lower per concrete input-shape signature,
        compile through the host, execute with flat numpy buffers, and
        rebuild the output pytree. The lowered module's parameter and
        result orders are the flattened pytree orders, which is what
        makes this correct for dict-carrying folds too. ``label`` (the
        executor cache key, when called from `cached`) attributes each
        per-shape host compile to its graph fingerprint in telemetry."""
        exe_cache: Dict[Tuple, Tuple] = {}

        def run(*args):
            import jax

            flat_in, in_tree = jax.tree_util.tree_flatten(args)
            flat_in = [np.asarray(a) for a in flat_in]
            shape_key = (
                in_tree,
                tuple((a.shape, str(a.dtype)) for a in flat_in),
            )
            entry = exe_cache.get(shape_key)
            if entry is None:
                import time as _time

                _t0 = _time.perf_counter()
                structs = jax.tree_util.tree_unflatten(
                    in_tree,
                    [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat_in],
                )
                # keep_unused: without it jit DCEs unused arguments out
                # of the module's parameter list and execution fails
                # with a buffer-count mismatch (e.g. the segment
                # aggregate's counts input when no fetch is a Mean).
                # Shardy is disabled for the lowering: the host's plugins
                # consume classic GSPMD StableHLO (custom_call @Sharding /
                # SPMDFullToShardShape), not the sdy dialect.
                with _LOWER_LOCK:
                    prev_sdy = jax.config.jax_use_shardy_partitioner
                    jax.config.update("jax_use_shardy_partitioner", False)
                    try:
                        lowered = jax.jit(traceable, keep_unused=True).lower(
                            *structs
                        )
                        mlir = str(lowered.compiler_ir(dialect="stablehlo"))
                    finally:
                        jax.config.update(
                            "jax_use_shardy_partitioner", prev_sdy
                        )
                out_flat, out_tree = jax.tree_util.tree_flatten(
                    lowered.out_info
                )
                out_specs = [
                    (tuple(o.shape), np.dtype(o.dtype)) for o in out_flat
                ]
                m = re.search(r"mhlo\.num_partitions = (\d+)", mlir)
                nparts = int(m.group(1)) if m else 1
                if nparts > self.host.device_count:
                    if getattr(self, "_allow_jax_fallback", False):
                        # the opted-in fallback covers this case too: a
                        # multi-device host that is still SMALLER than
                        # the program's partition count executes via the
                        # in-process JAX backend (the traceable is the
                        # already-jitted mesh program)
                        entry = ("jax", traceable, None)
                        exe_cache[shape_key] = entry
                    else:
                        raise RuntimeError(
                            f"program wants {nparts} partitions but the "
                            f"native host has {self.host.device_count} "
                            "device(s); construct NativeExecutor(devices=N) "
                            "with a multi-device plugin, or opt into "
                            "jax_fallback=True"
                        )
                else:
                    exe = self.host.compile(mlir)
                    with self._lock:  # += is not atomic; keep exact
                        self.compile_count += 1
                    entry = (exe, out_specs, out_tree)
                    exe_cache[shape_key] = entry
                    # each (program, shape signature) is one real host
                    # compile — attribute it like the jit "xla" phase
                    from ..utils import telemetry as _tele

                    _t1 = _time.perf_counter()
                    _tele.record_compile(
                        label[1] if label else getattr(
                            traceable, "__name__", "<fn>"
                        ),
                        label[0] if label else "fn",
                        _t1 - _t0,
                        "native",
                        _t0,
                        _t1,
                    )
                    # cost ledger: the Lowered is already in hand here,
                    # so modeled flops/bytes cost one HLO cost analysis
                    from . import costmodel as _cm

                    if _cm.enabled():
                        _cm.capture(
                            self._ledger_key(label, traceable),
                            None, args, lowered=lowered, phase="native",
                        )
            from . import costmodel as _cm

            if entry[0] == "jax":
                out = entry[1](*args)
                # the opted-in fallback has no Lowered to capture cost
                # from, but its executions still count — the program
                # stays visible in the ledger with honest None cost
                if _cm.enabled():
                    _cm.note_exec(
                        self._ledger_key(label, traceable), args, out
                    )
                return out
            exe, out_specs, out_tree = entry
            outs = exe(*flat_in, out_specs=out_specs)
            out = jax.tree_util.tree_unflatten(out_tree, outs)
            if _cm.enabled():
                _cm.note_exec(self._ledger_key(label, traceable), args, out)
            return out

        return run

    def cached(self, kind, graph, fetches, feed_names, make):
        if (
            kind.startswith(_MESH_KIND_PREFIXES)
            and self.host.device_count <= 1
        ):
            # A single-device host cannot satisfy a multi-partition
            # program. Mesh execution then needs the in-process JAX
            # executor — but running a JAX backend next to a native host
            # that owns the same device is unsafe (double TPU client),
            # so it is strictly opt-in.
            if not getattr(self, "_allow_jax_fallback", False):
                raise NotImplementedError(
                    f"this NativeExecutor's host has one device; {kind!r} "
                    "(shard_map over a mesh) needs either a multi-device "
                    "plugin (NativeExecutor(devices=N)) or the in-process "
                    "JAX executor. Construct NativeExecutor("
                    "jax_fallback=True) ONLY if the JAX backend does not "
                    "own the same device as the native host."
                )
            if self._jax_fallback is None:
                from .executor import Executor

                self._jax_fallback = Executor()
            return self._jax_fallback.cached(
                kind, graph, fetches, feed_names, make
            )
        key = (kind, graph.fingerprint(), tuple(fetches), tuple(feed_names))
        from .. import config as _config
        from .executor import lru_get_or_insert

        # the shared locked-LRU discipline (evicted wrappers free their
        # PJRT executables via NativeExecutable.__del__ once no call
        # holds them). `make()` hands back a jax.jit-wrapped program —
        # used purely as a lowering recipe; execution never touches the
        # in-process JAX backend.
        fn, inserted = lru_get_or_insert(
            self._cache, self._lock, key,
            lambda: self._native_run(make(), label=key),
            _config.get().executor_cache_entries,
        )
        with self._lock:  # mirror Executor.cached's hit/miss accounting
            if inserted:
                self.cache_misses += 1
            else:
                self.cache_hits += 1
        from . import executor as _exmod

        if _exmod._fault_injector is not None:  # shared injection seam
            fn = _exmod._fault_injector(fn, key)
        return fn

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
            lambda: build_callable(graph, list(fetches), list(feed_names)),
        )

    def cache_keys(self):
        """Interface parity with `Executor.cache_keys` (live compile-cache
        key snapshot; the fusion tests count kinds through it)."""
        with self._lock:
            return list(self._cache.keys())

    def jit_shape_compiles(self) -> int:
        """Interface parity with `Executor.jit_shape_compiles`. The
        native host compiles one executable per (program, input shape
        signature) — and `compile_count` increments on exactly those
        compiles — so here the two metrics coincide."""
        return int(self.compile_count)

    def run(
        self,
        graph: Graph,
        fetches: Sequence[str],
        feeds: Dict[str, np.ndarray],
        materialize: bool = False,
    ):
        """Mirror of `Executor.run`'s contract. The native host's
        execute already lands results in host buffers (its D2H is part
        of the call), so both modes return numpy; ``materialize`` exists
        so callers can be executor-agnostic about the boundary."""
        feed_names = sorted(feeds)
        fn = self.callable_for(graph, fetches, feed_names)
        out = fn(*[feeds[n] for n in feed_names])
        if materialize:
            return [np.asarray(o) for o in out]
        return list(out)
