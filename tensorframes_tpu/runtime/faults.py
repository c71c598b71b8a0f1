"""Fault-tolerant dispatch: error classification, classified retries, splits.

The reference outsourced ALL fault tolerance to Spark's task retry +
lineage recomputation (SURVEY §5: worker kernels are pure functions of
(broadcast graph, partition rows), so a failed task is simply re-run).
The port preserved the purity but replaced Spark's supervisor with a
blanket un-classified retry at a single call site. This module is the
real supervisor:

- **Classification** (`classify`): every dispatch exception is one of

  - ``transient`` — device lost/preempted, dropped device RPC, the
    UNAVAILABLE/INTERNAL/DATA_LOSS/ABORTED XlaRuntimeError status
    families. Re-running the pure block function is expected to
    succeed; these are retried with exponential backoff and (under the
    block scheduler) device failover.
  - ``resource`` — RESOURCE_EXHAUSTED / out-of-memory. Re-running the
    identical dispatch would fail identically; the dispatch sites
    instead SPLIT the block in half down the bucket ladder and combine
    the halves (row-local maps concatenate, classified monoid reduces
    combine via `combine_split_partials`).
  - ``deterministic`` — everything else (shape/dtype mismatches,
    ``FloatingPointError`` from ``check_numerics``, user-graph bugs).
    The original exception surfaces after EXACTLY ONE attempt; burning
    a retry budget on a deterministic error only delays the traceback.

- **Classified retry** (`FaultScope` / `run_with_retries`): per-verb
  retry budget (``config.verb_retry_budget``) on top of the per-block
  attempt cap (``config.block_retry_attempts``), exponential backoff
  (``retry_backoff_base_s`` doubling up to ``retry_backoff_max_s``)
  with DETERMINISTIC seeded jitter — two runs of the same failing
  workload sleep the same schedule, so chaos tests and the injection
  harness reproduce bit-for-bit.

- **Fault ledger** (`ledger_snapshot`): process-wide counts by class,
  plus retries/splits/evictions/fail-fasts — merged into
  `executor_stats()` and rendered by `tfs.diagnostics()`. The same
  events feed the always-live telemetry counters
  ``fault_retries{class=}`` / ``device_evictions`` / ``block_splits``.

- **Device-grant watchdog** (`device_grant`): backend init that hangs
  acquiring devices times out on a watchdog thread and raises
  `DeviceGrantTimeout` naming the budget instead of wedging the process
  forever; devices of another backend are used only where the caller
  passes its own ``fallback=``.

Injected faults from `tensorframes_tpu.testing.faults` carry an
explicit ``tfs_fault_class`` attribute, which `classify` honors before
any pattern matching — the harness and the production path share one
classifier by construction.
"""

from __future__ import annotations

import threading
import time
import zlib
from collections import deque as _deque
from typing import Callable, Dict, Optional, Sequence

from ..utils.log import get_logger

__all__ = [
    "TRANSIENT",
    "RESOURCE",
    "DETERMINISTIC",
    "classify",
    "backoff_delay",
    "FaultScope",
    "scope",
    "run_with_retries",
    "combine_split_partials",
    "note_split",
    "record_oom",
    "forensics_snapshot",
    "ledger_snapshot",
    "reset_ledger",
    "device_grant",
    "maybe_check_numerics",
]

_log = get_logger("faults")

TRANSIENT = "transient"
RESOURCE = "resource"
DETERMINISTIC = "deterministic"
_CLASSES = (TRANSIENT, RESOURCE, DETERMINISTIC)


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------

# absl-Status code tokens of the retryable families, matched as
# STATUS-SHAPED prefixes ("UNAVAILABLE: ..." — always rendered with a
# colon) so an arbitrary RuntimeError whose prose merely contains the
# word ("worker thread aborted") is never retried.
_STATUS_TOKENS = (
    "UNAVAILABLE",          # backend went away
    "INTERNAL",             # TPU runtime hiccups
    "DATA_LOSS",
    "ABORTED",
    "DEADLINE_EXCEEDED",
)

# Looser phrases, trusted ONLY on genuine XLA/JAX runtime exception
# types (and connection errors) — those messages come from the runtime,
# not from user code, so prose matching is safe there.
_TRANSIENT_PHRASES = (
    "DEVICE LOST",
    "DEVICE IS LOST",
    "PREEMPT",              # preempted / preemption
    "SOCKET CLOSED",
    "CONNECTION RESET",
    "HEARTBEAT",
)

_RESOURCE_PATTERNS = (
    "RESOURCE_EXHAUSTED",
    "RESOURCE EXHAUSTED",
    "OUT OF MEMORY",
    "OOM ",
    "OOM:",
    "ALLOCATION FAILURE",
    "FAILED TO ALLOCATE",
)

# Exception families whose MESSAGES are trusted for status-token
# classification: the XLA runtime surfaces everything as
# XlaRuntimeError/JaxRuntimeError (RuntimeError subclasses), and
# distributed/IO layers as OSError (ConnectionError, TimeoutError).
# A ValueError carrying "UNAVAILABLE" in user text stays deterministic.
_XLA_NAMES = ("XlaRuntimeError", "JaxRuntimeError")


def _runtimeish(exc: BaseException) -> bool:
    if isinstance(exc, (RuntimeError, OSError)):
        return True
    return any(t.__name__ in _XLA_NAMES for t in type(exc).__mro__)


def _xla_typed(exc: BaseException) -> bool:
    """A genuine runtime-owned exception (XLA/JAX runtime error class,
    or a connection failure) — the only types whose message PROSE is
    trusted, not just status-code prefixes."""
    if isinstance(exc, ConnectionError):
        return True
    return any(t.__name__ in _XLA_NAMES for t in type(exc).__mro__)


def classify(exc: BaseException) -> str:
    """Classify one dispatch exception as ``transient`` | ``resource``
    | ``deterministic``. Honors an explicit ``tfs_fault_class``
    attribute first (the injection harness stamps it), then
    `MemoryError`, then XLA status-code prefixes on runtime-ish
    exception types (plus runtime-owned phrases on genuine
    XlaRuntimeError/JaxRuntimeError/connection types). Everything
    unrecognized is deterministic — the conservative default: an
    unknown error is surfaced, never silently re-run."""
    tagged = getattr(exc, "tfs_fault_class", None)
    if tagged in _CLASSES:
        return tagged
    if isinstance(exc, MemoryError):
        return RESOURCE
    if _runtimeish(exc):
        msg = str(exc).upper()
        if any(p in msg for p in _RESOURCE_PATTERNS):
            return RESOURCE
        if any(f"{t}:" in msg for t in _STATUS_TOKENS):
            return TRANSIENT
        if _xla_typed(exc) and any(p in msg for p in _TRANSIENT_PHRASES):
            return TRANSIENT
    return DETERMINISTIC


# ---------------------------------------------------------------------------
# fault ledger (process-wide; surfaced via executor_stats/diagnostics)
# ---------------------------------------------------------------------------

_LEDGER_KEYS = (
    "transient", "resource", "deterministic",  # classified failures seen
    "retries", "splits", "evictions", "failfast", "grant_timeouts",
    "deadlines", "shed",  # runtime.deadline: budget expiries + admission sheds
)
_ledger_lock = threading.Lock()
_ledger: Dict[str, int] = {k: 0 for k in _LEDGER_KEYS}


def _note(key: str, n: int = 1) -> None:
    with _ledger_lock:
        _ledger[key] = _ledger.get(key, 0) + n


def note_eviction() -> None:
    """Scheduler hook: one device circuit opened (ledger only; the
    labeled ``device_evictions`` counter is the scheduler's)."""
    _note("evictions")


def note_transient_retry() -> None:
    """Ledger + counter for a transient retry performed OUTSIDE
    `FaultScope.dispatch` (e.g. the combine's donation-aware manual
    retry in `api._combine_partials`)."""
    _note(TRANSIENT)
    _note("retries")
    from ..utils import telemetry as _tele

    _tele.counter_inc("fault_retries", 1.0, **{"class": TRANSIENT})


def note_deadline() -> None:
    """Ledger hook for `runtime.deadline`: one verb ran out its time
    budget (the labeled ``deadline_exceeded{verb=}`` counter is
    incremented by the scope that raised)."""
    _note("deadlines")


def note_shed() -> None:
    """Ledger hook for `runtime.deadline`: admission control shed one
    verb (the ``verbs_shed`` counter is the controller's)."""
    _note("shed")


def note_split(verb: str) -> None:
    """One OOM block split performed by ``verb`` (ledger + the
    always-live ``block_splits`` counter; the split IS the resource
    class's retry, so it counts under ``fault_retries{class=resource}``
    too)."""
    _note("splits")
    _note("retries")
    from ..utils import telemetry as _tele

    _tele.counter_inc("block_splits", 1.0, verb=verb)
    _tele.counter_inc("fault_retries", 1.0, **{"class": RESOURCE})


def ledger_snapshot() -> Dict[str, int]:
    """The fault ledger: classified failure counts plus what was done
    about them (retries / splits / device evictions / fail-fasts /
    grant timeouts). Merged into ``executor_stats()['faults']``
    (which appends the OOM forensic snapshots under ``forensics``)."""
    with _ledger_lock:
        return dict(_ledger)


def reset_ledger() -> None:
    with _ledger_lock:
        for k in list(_ledger):
            _ledger[k] = 0
        _forensics.clear()


# ---------------------------------------------------------------------------
# OOM forensics: what was resident when a dispatch ran out of memory
# ---------------------------------------------------------------------------

def _tag_fault(e: BaseException, cls: str) -> None:
    """Stamp the final classification onto an exception about to
    escape a `FaultScope` for good — downstream layers (the flight
    recorder's `capture_escape`, serving's status mapping) distinguish
    a classified runtime fault from a plain user error by this
    attribute, and re-classifying at each layer could disagree."""
    if getattr(e, "tfs_fault_class", None) is None:
        try:
            e.tfs_fault_class = cls
        except Exception:
            pass  # __slots__ errors refuse stamps; e still raises


# bounded: OOMs are rare, and a flapping device must not grow an
# unbounded evidence log — the freshest window is the useful one
_FORENSICS_MAX = 16
_forensics: "_deque" = _deque(maxlen=_FORENSICS_MAX)


def record_oom(
    verb: str,
    program,
    rows: int,
    depth: int,
    decision: str,
    error: BaseException,
    bucket: Optional[int] = None,
) -> None:
    """Capture a forensic snapshot for one ``resource``-classified
    dispatch: the failing program, its cost-ledger modeled footprint,
    the live-buffer / memory_stats state per device AT FAULT TIME, the
    block's row range + bucket rung, and the split decision
    (``"split"`` — the runtime is about to halve the range — or a
    ``"reraise:*"`` reason when splitting is ineligible). Turns a
    silent degradation event into an explainable one: surfaced in
    ``executor_stats()['faults']['forensics']`` and rendered by
    `tfs.diagnostics()`. Never raises — forensics must not worsen the
    failure it documents."""
    try:
        from . import costmodel as _cm

        snap = {
            "verb": str(verb),
            "program": str(program),
            "rows": int(rows),
            "bucket": int(bucket) if bucket is not None else None,
            "depth": int(depth),
            "decision": str(decision),
            "error": f"{type(error).__name__}: {str(error)[:200]}",
            "modeled": _cm.program_footprint(program),
            "devices": _cm.memory_overview(),
        }
    except Exception:  # degraded snapshot beats no snapshot
        snap = {
            "verb": str(verb),
            "program": str(program),
            "rows": int(rows),
            "bucket": None,
            "depth": int(depth),
            "decision": str(decision),
            "error": type(error).__name__,
            "modeled": None,
            "devices": [],
        }
    with _ledger_lock:
        _forensics.append(snap)
    try:
        from ..utils import telemetry as _tele

        _tele.counter_inc("oom_forensics", 1.0, verb=str(verb))
    except Exception:
        pass  # forensics must not worsen the failure it documents
    if not str(decision).startswith("split"):
        # split exhaustion / ineligibility: the resource fault is about
        # to ESCAPE — this one-off snapshot is exactly what the flight
        # recorder generalizes, so the full bundle rides along
        try:
            from . import blackbox as _blackbox

            _tag_fault(error, RESOURCE)
            _blackbox.capture(
                "oom", error, verb=str(verb), program=str(program),
                extra={"oom": snap},
            )
        except Exception:
            pass  # the recorder must not worsen the failure either


def forensics_snapshot() -> list:
    """The bounded OOM forensic log, oldest first."""
    with _ledger_lock:
        return [dict(s) for s in _forensics]


# ---------------------------------------------------------------------------
# backoff
# ---------------------------------------------------------------------------


def backoff_delay(
    attempt: int,
    what: str = "",
    base: Optional[float] = None,
    cap: Optional[float] = None,
    jitter: Optional[float] = None,
    seed: Optional[int] = None,
) -> float:
    """Delay before transient retry ``attempt`` (1-based): exponential
    ``base * 2^(attempt-1)`` capped at ``cap``, times a DETERMINISTIC
    jitter factor in ``[1, 1+jitter]`` seeded from ``(seed, what,
    attempt)`` — reruns of the same failing dispatch sleep the same
    schedule, so fault-injected tests are reproducible while distinct
    blocks still decorrelate."""
    from .. import config as _config

    cfg = _config.get()
    base = cfg.retry_backoff_base_s if base is None else base
    cap = cfg.retry_backoff_max_s if cap is None else cap
    jitter = cfg.retry_jitter if jitter is None else jitter
    seed = cfg.retry_seed if seed is None else seed
    delay = min(float(cap), float(base) * (2.0 ** max(0, attempt - 1)))
    if jitter:
        # crc32 keyed by (seed, what, attempt): stable across processes
        # (unlike hash(), which randomizes strings per interpreter)
        h = zlib.crc32(f"{seed}|{what}|{attempt}".encode())
        delay *= 1.0 + float(jitter) * ((h & 0xFFFF) / 65535.0)
    return delay


# ---------------------------------------------------------------------------
# classified retry
# ---------------------------------------------------------------------------


class FaultScope:
    """One verb call's fault-handling state: the per-block attempt cap
    and the verb-wide retry budget. Sites create one scope per verb
    call and route every block dispatch through `dispatch`."""

    def __init__(
        self,
        verb: str,
        attempts: Optional[int] = None,
        budget: Optional[int] = None,
    ):
        from .. import config as _config

        cfg = _config.get()
        self.verb = verb
        self.attempts = (
            cfg.block_retry_attempts if attempts is None else int(attempts)
        )
        self.budget = (
            cfg.verb_retry_budget if budget is None else int(budget)
        )

    def dispatch(
        self,
        thunk: Callable[[], object],
        what: str = "block",
        sched=None,
        index: Optional[int] = None,
        sleep: Optional[Callable[[float], None]] = None,
    ):
        """Run a zero-arg dispatch ``thunk`` with classified fault
        handling:

        - ``deterministic`` → re-raise after exactly one attempt;
        - ``resource`` → re-raise immediately (the CALLER owns block
          splitting — it needs the feed slices and the combine recipe);
        - ``transient`` → evict the failing device from the schedule
          (``sched``/``index`` given: circuit-breaks the device and
          re-places its unissued blocks — see `BlockSchedule.evict`),
          sleep the deterministic backoff, and re-invoke the thunk —
          `BlockSchedule.bind` reads the slot at call time, so the
          retry lands on the re-placed device. Gives up when the
          per-block attempts or the verb budget run out and re-raises
          the last transient error.

        Every attempt starts with a cooperative deadline/cancel check
        (`runtime.deadline.check`): a verb past its budget stops
        issuing dispatches at the next boundary, and the escaping
        `DeadlineExceeded` is stamped with the schedule's partial-work
        accounting (``tfs_blocks_issued`` / ``tfs_blocks_unissued``).
        The default backoff ``sleep`` is the deadline-aware
        interruptible wait — it wakes on cancellation and CLIPS to the
        remaining budget, so a timed-out verb never sleeps past its
        deadline (an explicit ``sleep=`` callable, used by tests,
        bypasses the clipping but not the per-attempt checks).
        """
        from ..utils import telemetry as _tele
        from . import deadline as _dl

        def _stamp_partial(e):
            if sched is not None and getattr(
                e, "tfs_blocks_issued", None
            ) is None:
                prog = getattr(sched, "progress", None)
                if callable(prog):
                    try:
                        p = prog()
                        e.tfs_blocks_issued = p["issued"]
                        e.tfs_blocks_unissued = p["unissued"]
                    except Exception:
                        pass  # __slots__ errors refuse stamps; e raises
            return e

        attempt = 0
        while True:
            try:
                _dl.check(what)
                return thunk()
            except (_dl.DeadlineExceeded, _dl.Cancelled) as e:
                # counted once at the raising scope (deadline ledger +
                # deadline_exceeded{verb=}) — not double-booked as a
                # classified dispatch failure here
                raise _stamp_partial(e)
            except Exception as e:  # noqa: BLE001 — classified below
                cls = classify(e)
                _note(cls)
                if cls != TRANSIENT:
                    if cls == DETERMINISTIC:
                        _note("failfast")
                    _tag_fault(e, cls)
                    raise
                if attempt >= self.attempts or self.budget <= 0:
                    _log.warning(
                        "%s: transient failure, retries exhausted "
                        "(attempt %d/%d, verb budget %d left): %s",
                        what, attempt + 1, self.attempts + 1,
                        self.budget, e,
                    )
                    _tag_fault(e, cls)
                    raise
                attempt += 1
                self.budget -= 1
                _note("retries")
                _tele.counter_inc(
                    "fault_retries", 1.0, **{"class": TRANSIENT}
                )
                evicted = None
                if sched is not None and index is not None:
                    evicted = sched.evict(index)
                delay = backoff_delay(attempt, what)
                _log.warning(
                    "%s: transient failure (attempt %d/%d)%s — retrying "
                    "in %.3fs: %s",
                    what, attempt, self.attempts + 1,
                    f", evicted device {evicted}" if evicted else "",
                    delay, e,
                )
                try:
                    with _tele.span(
                        "fault.retry", kind="fault", what=what,
                        attempt=attempt, device=evicted,
                        **{"class": TRANSIENT},
                    ):
                        if sleep is not None:
                            sleep(delay)
                        else:
                            _dl.sleep_interruptible(
                                delay, f"{what} (backoff)"
                            )
                except (_dl.DeadlineExceeded, _dl.Cancelled) as de:
                    raise _stamp_partial(de)


def scope(
    verb: str,
    attempts: Optional[int] = None,
    budget: Optional[int] = None,
) -> FaultScope:
    """One `FaultScope` per verb call (reads the config at entry, so a
    scoped ``config.override`` covers the whole verb)."""
    return FaultScope(verb, attempts=attempts, budget=budget)


def run_with_retries(
    fn: Callable,
    *args,
    attempts: int = 0,
    what: str = "block",
    verb: Optional[str] = None,
    sleep: Optional[Callable[[float], None]] = None,
):
    """Classified drop-in for the old blanket retry: call ``fn(*args)``;
    TRANSIENT errors get up to ``attempts`` extra attempts with
    backoff, ``resource``/``deterministic`` errors surface after
    exactly one attempt (the CHANGED semantics — the old version burned
    every attempt on a `FloatingPointError` before re-raising it). The
    standalone form for single-dispatch sites (mesh programs, combines,
    segment aggregation) that have no schedule to fail over."""
    s = FaultScope(verb or what, attempts=attempts)
    return s.dispatch(lambda: fn(*args), what=what, sleep=sleep)


# ---------------------------------------------------------------------------
# OOM split support
# ---------------------------------------------------------------------------


def split_allowed(n_rows: int, depth: int) -> bool:
    """A resource-classified block of ``n_rows`` at recursion ``depth``
    may split once more: at least 2 rows to halve, and bounded depth
    (``config.oom_split_depth``) so a genuinely-too-small memory budget
    degenerates into the original error, not infinite recursion."""
    from .. import config as _config

    return n_rows > 1 and depth < _config.get().oom_split_depth


def combine_split_partials(
    combiners: Sequence[str],
    left: Sequence,
    right: Sequence,
    n_left: int,
    n_right: int,
):
    """Monoid-combine the per-fetch partials of a split reduce block:
    ``sum``→add, ``prod``→multiply, ``min``/``max``→elementwise, and
    ``mean``→row-count-weighted average (exact: the halves partition
    the block's rows). Only graphs the chunk classifier
    (`aggregate._chunk_combiners`) proved reducible this way ever reach
    a split — unclassifiable reduces re-raise the original OOM."""
    import jax.numpy as jnp

    out = []
    for comb, a, b in zip(combiners, left, right):
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        if comb == "sum":
            out.append(a + b)
        elif comb == "prod":
            out.append(a * b)
        elif comb == "min":
            out.append(jnp.minimum(a, b))
        elif comb == "max":
            out.append(jnp.maximum(a, b))
        elif comb == "mean":
            w = float(n_left + n_right)
            out.append(
                (
                    a * jnp.asarray(n_left / w, a.dtype)
                    + b * jnp.asarray(n_right / w, a.dtype)
                ).astype(a.dtype)
            )
        else:  # pragma: no cover - classifier emits only the tags above
            raise AssertionError(f"unknown combiner {comb!r}")
    return tuple(out)


# ---------------------------------------------------------------------------
# device-grant watchdog
# ---------------------------------------------------------------------------

_grant_lock = threading.Lock()
_grant_granted = False        # a grab succeeded: skip the watchdog thread
_grant_fallback = None        # a grab timed out: the cached fallback devices
_grant_warned = False


class DeviceGrantTimeout(TimeoutError):
    """The device-grant watchdog's budget ran out and the caller gave no
    ``fallback=``: the verb fails instead of running somewhere else."""

    # retrying would wait out the same budget on the same wedged grant
    tfs_fault_class = "deterministic"


def _reset_grant_state() -> None:  # test hook
    global _grant_granted, _grant_fallback, _grant_warned
    with _grant_lock:
        _grant_granted = False
        _grant_fallback = None
        _grant_warned = False


def device_grant(
    grab: Optional[Callable[[], Sequence]] = None,
    timeout_s: Optional[float] = None,
    fallback: Optional[Callable[[], Sequence]] = None,
):
    """Acquire devices under a watchdog: run ``grab()`` (default
    ``jax.local_devices``) on a daemon thread and wait ``timeout_s``
    (default ``config.device_grant_timeout_s``). On timeout — backend
    init wedged at the device grant — count ``device_grant_timeouts``
    and raise `DeviceGrantTimeout` naming the budget: the runtime never
    substitutes another backend's devices by itself (a verb that
    quietly ran on the CPU would be read as a chip result). Only a
    caller that passes its own ``fallback=`` gets devices back after a
    timeout; that result is warned about once and cached (the wedged
    grab thread is left parked on its daemon thread — re-probing it
    every call would spawn a thread per verb). A successful grab is
    remembered, so steady-state calls cost one flag read and no
    thread."""
    global _grant_granted, _grant_fallback, _grant_warned
    from .. import config as _config

    if grab is None:
        import jax

        grab = jax.local_devices
    if timeout_s is None:
        timeout_s = _config.get().device_grant_timeout_s
    # an active verb deadline bounds the grant too (min of the two
    # budgets): a verb with 0.5s left must not wait a 30s watchdog —
    # and with the watchdog OFF, the deadline alone arms it, so a
    # deadlined verb can never wedge at device acquisition
    from . import deadline as _deadline

    _deadline.check("device_grant")
    _rem = _deadline.remaining()
    deadline_clipped = False
    if _rem is not None and (
        not timeout_s or timeout_s <= 0 or _rem < timeout_s
    ):
        # the DEADLINE, not the watchdog config, bounds this wait: a
        # timeout here means the verb ran out of budget, NOT that the
        # backend is wedged — it must surface as DeadlineExceeded and
        # must never poison the process-wide fallback cache (a healthy
        # backend that merely initializes slower than one verb's
        # remaining budget would otherwise pin every future verb to a
        # caller's fallback devices forever)
        timeout_s = _rem
        deadline_clipped = True
    with _grant_lock:
        if _grant_fallback is not None:
            return list(_grant_fallback)
        granted = _grant_granted
    if granted or not timeout_s or timeout_s <= 0:
        out = grab()
        with _grant_lock:
            _grant_granted = True
        return list(out)

    box: dict = {}
    done = threading.Event()

    def _worker():
        try:
            box["devices"] = grab()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(
        target=_worker, daemon=True, name="tfs-device-grant"
    )
    t.start()
    if done.wait(float(timeout_s)):
        if "error" in box:
            raise box["error"]
        with _grant_lock:
            _grant_granted = True
        return list(box["devices"])

    if deadline_clipped:
        # verb budget exhausted while the grant was still in flight:
        # raise the verb's own typed deadline (check() observes the
        # now-expired scope) — no warning, no counter, and above all
        # NO cached fallback. The grab thread parks on its daemon
        # thread; a later verb with a real budget re-probes cleanly.
        _deadline.check("device_grant")
        raise TimeoutError(  # pragma: no cover - clock-skew backstop
            "device grant outlived the verb deadline"
        )

    # wedged at grant
    _note("grant_timeouts")
    from ..utils import telemetry as _tele

    _tele.counter_inc("device_grant_timeouts")
    if fallback is None:
        raise DeviceGrantTimeout(
            f"device grant did not complete within {float(timeout_s):.1f}s "
            "(config.device_grant_timeout_s / TFS_DEVICE_GRANT_TIMEOUT_S): "
            "the accelerator backend appears wedged at device acquisition"
        )

    try:
        fb = list(fallback())
    except Exception as e:
        raise TimeoutError(
            f"device grant did not complete within {timeout_s}s "
            f"(config.device_grant_timeout_s) and the fallback failed: "
            f"{type(e).__name__}: {e}"
        ) from e
    with _grant_lock:
        _grant_fallback = list(fb)
        warned = _grant_warned
        _grant_warned = True
    if not warned:
        _log.warning(
            "device grant did not complete within %.1fs "
            "(config.device_grant_timeout_s / TFS_DEVICE_GRANT_TIMEOUT_S)"
            " — the accelerator backend appears WEDGED at device "
            "acquisition; using the caller's fallback (%d device(s)) "
            "for this process.",
            float(timeout_s), len(fb),
        )
    return list(fb)


# ---------------------------------------------------------------------------
# numerics guard (moved here from the retired runtime.retry shim: the
# blanket-retry module it shared is long gone — failure HANDLING and
# failure DETECTION now live in one place)
# ---------------------------------------------------------------------------


def maybe_check_numerics(fetch_names, outs, what: str):
    """Debug-mode numerics guard (``tfs.config.update(check_numerics=True)``):
    raise FloatingPointError naming the verb, block, and fetch when an
    output contains NaN/Inf — the role `CheckNumerics` nodes play in the
    reference's graphs, applied to every fetch without editing the graph.

    The finite-mask reduction runs ON DEVICE: every float fetch folds to
    one boolean, the booleans fold to one scalar verdict, and the clean
    path pays exactly ONE host sync for that scalar — the outputs
    themselves never leave device memory. Only when the verdict fires
    does the failure path sync per fetch to name the culprit and count
    its bad values (also reduced on device). Off by default."""
    from .. import config

    if not config.get().check_numerics:
        return
    import jax.numpy as jnp

    finites = []  # (name, array, all-finite scalar) per float fetch
    for name, o in zip(fetch_names, outs):
        arr = jnp.asarray(o)
        if not jnp.issubdtype(arr.dtype, jnp.floating):
            continue
        finites.append((name, arr, jnp.all(jnp.isfinite(arr))))
    if not finites:
        return
    verdict = (
        finites[0][2]
        if len(finites) == 1
        else jnp.all(jnp.stack([f for _, _, f in finites]))
    )
    if bool(verdict):  # the one sync on the clean path
        return
    for name, arr, fin in finites:
        if not bool(fin):
            bad = int(jnp.sum(~jnp.isfinite(arr)))
            raise FloatingPointError(
                f"{what}: fetch {name!r} contains {bad} non-finite "
                "value(s) (check_numerics is on)"
            )
    raise AssertionError("unreachable: verdict fired but no fetch did")
