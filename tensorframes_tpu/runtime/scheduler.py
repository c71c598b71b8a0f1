"""Multi-device block scheduler: data-parallel dispatch of blocks.

The reference ran one TF session per Spark partition on whatever
executor the cluster handed it, WHERE THE PARTITION LIVED; the port's
non-mesh verbs inherited a single-device analogue — every per-block jit
dispatch landed on the default JAX device. Blocks are an embarrassingly
parallel unit of work; this module places them.

Placement looks first at where the data is. HOME PLAN: a row-local map
(`map_blocks`' graph route, a program `shape.classify` found row-local)
whose feed columns all live whole on ONE device of the set, under a
device set the scheduler chose itself (``config.block_scheduler``, not
``devices=``), plans every non-empty block on that device. Such a
program reads each row once and writes it once: HBM feeds it several
times faster than any link out of the chip, so moving a block to an
idle chip and its result back costs more than computing it at home,
whatever the other chips are doing. The blocks of a home plan are
neighbours on one device, so a run of equal ones is one group
(`shape_policy.group_dispatch`), as it is with no schedule, and the
output stays with the columns it is appended to. Nothing is sorted for
a home plan, and no knob, device kind or bandwidth constant decides it:
the planner's input is where the feeds are and the class the program
was given. A home whose failover circuit is open is not in the set, and
its blocks spread over the healthy devices as below.

Everything else (an explicit ``devices=``: the user's placement, block
for block; numpy, sharded or scattered columns: blocks with no home;
programs not proven row-local, ``trim``, bound values, the function
front end, `map_rows`, the reduce verbs and the stream) is placed
size-aware largest-first (LPT greedy): blocks sorted by row count
descending are assigned one at a time to the least-loaded device, which
bounds the makespan at 4/3 OPT and — crucially — is DETERMINISTIC, so a
re-run dispatches every block to the same device and compiles nothing
new. The dispatch loop itself stays in block order: assignment decides
*where*, never *when*, so partial lists keep their block order and
ordering-sensitive tests/semantics are untouched.

Execution placement rides jax's committed-input semantics: each block's
feeds are `jax.device_put` onto the assigned device (async; H2D copies
to different devices overlap) and the jitted program runs where its
inputs live. The executor cache entry is shared across devices — the
per-device program specialization happens in jit's own cache, which
keys on the committed device exactly as it keys on shape (the same
mechanism `shape_policy` leans on for bucketing), so per-device compile
counts are visible through `jit_shape_compiles` and bounded by
``ndev x`` the single-device count (``ndev x`` ladder rungs under
bucketing).

Reduce verbs fold per-device partials locally and run ONE final
cross-device combine on the anchor device (associative direct monoid
graphs only — see `api._combine_partials_scheduled`); everything stays
an async device op, so the number of host syncs does not grow.

Scheduling turns on via ``config.block_scheduler`` /
``TFS_BLOCK_SCHEDULER`` ("auto": on when >1 local device) or an
explicit ``devices=`` override on any non-mesh verb; ``mesh=`` always
takes precedence (a mesh owns its own placement). The native executor
(`NativeExecutor.supports_scheduling = False`) is never scheduled — it
owns its own PJRT host and `device_put` would initialize the
in-process JAX backend next to it.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "BlockSchedule",
    "DeviceHealth",
    "device_health",
    "device_label",
    "global_device_set",
    "global_mode",
    "health_overview",
    "plan",
    "resolve",
    "schedule_for",
    "schedule_weights",
]

_MODES = ("auto", "on", "off", "global")


# ---------------------------------------------------------------------------
# device health: the failover circuit breaker
# ---------------------------------------------------------------------------


class DeviceHealth:
    """Per-device circuit breaker keyed by device label.

    State machine per device: *closed* (healthy — no entry in the
    table) → a transient dispatch failure OPENS the circuit for
    ``config.device_cooldown_s`` (doubling on repeated failures, capped
    at 8x) → after the cooldown the next `usable` check transitions to
    *half-open* and admits the device to ONE probing schedule (that
    check's caller; further `usable` checks exclude it again until the
    probe reaches a verdict, re-arming after another cooldown in case
    the probing schedule never dispatched to it) → a successful
    dispatch closes the circuit (entry removed), a failure re-opens it
    with the doubled cooldown. `resolve` filters circuit-open devices
    out of auto/on scheduling, so an evicted device's remaining blocks
    re-place onto healthy devices; explicit ``devices=`` pins bypass
    the filter (loudly).

    All timestamps ride an injectable ``now`` (monotonic seconds) so
    the state machine unit-tests without sleeping."""

    def __init__(self):
        self._lock = threading.Lock()
        self._states: Dict[str, Dict] = {}

    def mark_failure(self, label: str, now: Optional[float] = None) -> None:
        """A transient dispatch failure on ``label``: open (or re-open,
        with doubled cooldown) its circuit and count the eviction."""
        from .. import config as _config
        from ..utils import telemetry as _tele
        from ..utils.log import get_logger
        from . import faults as _faults

        now = time.monotonic() if now is None else now
        base = max(1e-3, float(_config.get().device_cooldown_s))
        with self._lock:
            st = self._states.get(label)
            if st is None:
                st = {
                    "state": "open", "failures": 0, "cooldown": base,
                    "until": 0.0, "warned_pin": False,
                }
                self._states[label] = st
            else:
                st["state"] = "open"
                st["cooldown"] = min(st["cooldown"] * 2.0, base * 8.0)
            st["failures"] += 1
            st["until"] = now + st["cooldown"]
            cooldown = st["cooldown"]
            failures = st["failures"]
        _faults.note_eviction()
        _tele.counter_inc("device_evictions", 1.0, device=label)
        get_logger("scheduler").warning(
            "device %s evicted after a transient dispatch failure; "
            "circuit open for %.1fs (half-open probe after cooldown)",
            label, cooldown,
        )
        try:
            # circuit-open eviction is an incident even though no
            # exception escapes (the work re-places); captured after
            # self._lock is released — the recorder does file I/O
            from . import blackbox as _blackbox

            _blackbox.capture(
                "eviction",
                extra={
                    "device": label, "failures": failures,
                    "cooldown_s": cooldown,
                },
            )
        except Exception:
            pass  # the recorder must never break an eviction path

    def mark_success(self, label: str) -> None:
        """A successful dispatch on ``label``: closes a half-open
        circuit (the probe passed). Fast path: no table entries, no
        lock contention — the steady state costs one dict check."""
        if not self._states:
            return
        with self._lock:
            st = self._states.get(label)
            if st is not None and st["state"] == "half-open":
                del self._states[label]

    def usable(self, label: str, now: Optional[float] = None) -> bool:
        """True when ``label`` may receive dispatches: circuit closed,
        or open-past-cooldown (transitions to half-open and admits ONE
        probing caller — later checks exclude the device again until
        the probe's verdict, re-arming after another cooldown so a
        probe that never dispatched cannot strand the device)."""
        if not self._states:
            return True
        now = time.monotonic() if now is None else now
        with self._lock:
            st = self._states.get(label)
            if st is None:
                return True
            if st["state"] == "open":
                if now >= st["until"]:
                    st["state"] = "half-open"
                    st["probe_rearm"] = now + st["cooldown"]
                    return True
                return False
            # half-open: the transition call above was the probe
            # admission; everyone else waits for the verdict (or for
            # the re-arm window, if the probing schedule never ran)
            if now >= st.get("probe_rearm", 0.0):
                st["probe_rearm"] = now + st["cooldown"]
                return True
            return False

    def filter(self, devices: Sequence, now: Optional[float] = None) -> List:
        return [d for d in devices if self.usable(device_label(d), now)]

    def table(self) -> List[Dict]:
        """Snapshot for `tfs.diagnostics()`: one row per non-closed
        circuit (an empty table means every device is healthy)."""
        now = time.monotonic()
        with self._lock:
            return [
                {
                    "device": label,
                    "state": st["state"],
                    "failures": st["failures"],
                    "cooldown_s": round(st["cooldown"], 3),
                    "retry_in_s": round(max(0.0, st["until"] - now), 3),
                }
                for label, st in sorted(self._states.items())
            ]

    def warn_pinned(self, label: str) -> bool:
        """Explicit ``devices=`` pins opt out of failover — but a pin
        onto a circuit-open device deserves one loud warning per
        episode. Returns True when the warning should fire."""
        with self._lock:
            st = self._states.get(label)
            if st is None or st["warned_pin"]:
                return False
            st["warned_pin"] = True
            return True

    def reset(self) -> None:
        with self._lock:
            self._states.clear()


_health = DeviceHealth()


def device_health() -> DeviceHealth:
    """The process-wide device-health registry (one circuit breaker per
    device label, shared by every schedule)."""
    return _health


def health_overview() -> List[Dict]:
    """One row per LOCAL device — healthy devices included (unlike
    `DeviceHealth.table`, which lists only tripped circuits): label,
    kind, circuit state (``closed`` / ``open`` / ``half-open``),
    failure count and remaining cooldown. The /healthz endpoint's
    payload; circuits for devices no longer local (a fallback set after
    a grant timeout) are appended so they stay visible."""
    by_label = {row["device"]: row for row in _health.table()}
    rows: List[Dict] = []
    try:
        devices = _local_devices()
    except Exception:
        devices = []
    seen = set()
    for d in devices:
        lab = device_label(d)
        seen.add(lab)
        tripped = by_label.get(lab)
        rows.append(
            {
                "device": lab,
                "device_kind": getattr(d, "device_kind", None),
                "state": tripped["state"] if tripped else "closed",
                "failures": tripped["failures"] if tripped else 0,
                "cooldown_s": tripped["cooldown_s"] if tripped else 0.0,
                "retry_in_s": tripped["retry_in_s"] if tripped else 0.0,
            }
        )
    for lab, tripped in sorted(by_label.items()):
        if lab not in seen:
            rows.append({"device_kind": None, **tripped})
    return rows


def device_label(dev) -> str:
    """The telemetry label for a device: ``platform:id`` (what dispatch
    spans, per-device executor stats and the queue-depth gauge key on)."""
    return f"{getattr(dev, 'platform', 'dev')}:{getattr(dev, 'id', '?')}"


def plan(weights: Sequence[int], ndev: int) -> List[Optional[int]]:
    """Size-aware largest-first placement: item indices sorted by weight
    descending (ties: lower index first) are greedily assigned to the
    least-loaded device slot (ties: lowest slot). Returns one slot per
    item; zero-weight items map to ``None`` (empty blocks are never
    dispatched, so they must not skew the load ledger)."""
    if ndev < 1:
        raise ValueError(f"plan needs >= 1 device, got {ndev}")
    order = sorted(range(len(weights)), key=lambda i: (-int(weights[i]), i))
    load = [0] * ndev
    out: List[Optional[int]] = [None] * len(weights)
    for i in order:
        w = int(weights[i])
        if w <= 0:
            continue
        slot = min(range(ndev), key=lambda s: (load[s], s))
        load[slot] += w
        out[i] = slot
    return out


def _local_devices() -> List:
    import jax

    from .. import config as _config
    from . import deadline as _dl

    t = _config.get().device_grant_timeout_s
    if (t and t > 0) or _dl.remaining() is not None:
        # device-grant watchdog: a wedged accelerator backend (stuck at
        # device grant) times out here and the verb raises
        # `faults.DeviceGrantTimeout` naming the budget instead of
        # hanging forever — it never carries on with the CPU backend's
        # devices (no `fallback=` is passed). An active verb DEADLINE
        # arms the watchdog too (min of the two budgets, applied inside
        # device_grant): a deadlined verb can never wedge at grant even
        # with the config watchdog off.
        from . import faults as _faults

        return list(
            _faults.device_grant(
                grab=jax.local_devices, timeout_s=t if t and t > 0 else None
            )
        )
    return list(jax.local_devices())


def _normalize_devices(devices) -> Tuple:
    """Explicit ``devices=``: accept jax Device objects or local-device
    indices; reject empty (an empty override means the caller's intent
    is unclear — pass None for auto or set block_scheduler='off')."""
    devs = list(devices)
    if not devs:
        raise ValueError(
            "devices=[] is ambiguous; pass None (config decides) or "
            "disable with config.block_scheduler='off'"
        )
    local = None
    out = []
    for d in devs:
        if isinstance(d, (int, np.integer)):
            if local is None:
                local = _local_devices()
            if not 0 <= int(d) < len(local):
                raise ValueError(
                    f"devices: index {int(d)} out of range for "
                    f"{len(local)} local device(s)"
                )
            out.append(local[int(d)])
        else:
            out.append(d)
    return tuple(out)


def global_mode() -> bool:
    """True when ``config.block_scheduler == "global"`` — eligible verb
    dispatches route through the `GlobalFrame` SPMD path; everything
    ineligible falls back to per-block scheduling (``resolve`` treats
    the mode as "auto" for that fallback)."""
    from .. import config as _config

    return _config.get().block_scheduler == "global"


def global_device_set() -> List:
    """The local devices a `GlobalFrame` data mesh spans: every local
    device whose failover circuit is closed. When circuit-open devices
    shrink the set, say so LOUDLY — a shrunk mesh changes sharding (and
    therefore which compiled program runs), which an operator debugging
    throughput must be able to see. All circuits open falls back to the
    full set (same last-resort rule as `resolve`)."""
    devs = _local_devices()
    healthy = _health.filter(devs)
    if not healthy:
        from ..utils.log import get_logger

        get_logger("scheduler").warning(
            "every local device's failover circuit is open; building "
            "the global-frame mesh over the full device set anyway"
        )
        return devs
    if len(healthy) < len(devs):
        from ..utils.log import get_logger

        get_logger("scheduler").warning(
            "global-frame mesh shrunk to %d of %d local device(s): "
            "%s circuit-open after transient failures",
            len(healthy), len(devs),
            ",".join(
                device_label(d) for d in devs if d not in healthy
            ),
        )
    return healthy


def resolve(
    devices=None, executor=None, mesh=None
) -> Optional[Tuple]:
    """The device set a verb call should schedule blocks over, or None
    when scheduling is off for this dispatch.

    Precedence: ``mesh=`` wins outright (the mesh path owns placement);
    an executor that does not opt in (`supports_scheduling`) is never
    scheduled — with an explicit ``devices=`` that is a loud error, not
    a silent drop; an explicit ``devices=`` list wins over the config;
    otherwise ``config.block_scheduler``: "off" disables, "on" schedules
    onto all local devices (even one — useful to force the scheduled
    code path), "auto" (default) schedules only when >1 local device
    exists. "global" behaves like "auto" HERE: the GlobalFrame SPMD
    routing happens above this call at the verb layer, and everything
    that falls through (ineligible graphs, small frames) still deserves
    per-block scheduling."""
    if mesh is not None:
        if devices is not None:
            raise ValueError(
                "devices= and mesh= are mutually exclusive; the mesh "
                "owns block placement"
            )
        return None
    supported = executor is None or getattr(
        executor, "supports_scheduling", False
    )
    if devices is not None:
        if not supported:
            raise ValueError(
                "devices= needs an executor that supports block "
                f"scheduling; {type(executor).__name__} does not (the "
                "native host owns its own device)"
            )
        devs = _normalize_devices(devices)
        # pins opt OUT of failover — loudly: a pin onto a circuit-open
        # device is deliberate placement, but the operator should know
        # the scheduler would have avoided it
        for d in devs:
            lab = device_label(d)
            if not _health.usable(lab) and _health.warn_pinned(lab):
                from ..utils.log import get_logger

                get_logger("scheduler").warning(
                    "devices= pins dispatches to %s, whose failover "
                    "circuit is OPEN after transient failures; explicit "
                    "pins bypass device failover",
                    lab,
                )
        return devs
    if not supported:
        return None
    from .. import config as _config

    mode = _config.get().block_scheduler
    if mode not in _MODES:
        # fail loud: a typo'd mode silently meaning "off" would defeat
        # the knob (same discipline as config.native_executor)
        raise ValueError(
            f"config.block_scheduler={mode!r} is not one of "
            "'auto' | 'on' | 'off' | 'global'"
        )
    if mode == "off":
        return None
    devs = _local_devices()
    if mode in ("auto", "global") and len(devs) < 2:
        return None
    # failover: circuit-open devices drop out of auto/on scheduling
    # until their cooldown elapses (then ONE half-open probe re-admits
    # them on success). With every device evicted there is nothing left
    # to fail over to: schedule the full set rather than nothing.
    healthy = _health.filter(devs)
    if not healthy:
        from ..utils.log import get_logger

        get_logger("scheduler").warning(
            "every local device's failover circuit is open; scheduling "
            "over the full device set anyway"
        )
        healthy = devs
    return tuple(healthy)


class BlockSchedule:
    """One verb call's placement: device set + per-item slot assignment.

    ``bind(i, fn)`` returns the dispatch callable for item ``i``: it
    `device_put`s the feeds onto the assigned device, invokes ``fn``
    (committed inputs place the execution), and keeps the per-device
    compile ledger on the executor. ``put(i, feeds)`` is the feeds-only
    half for callers that invoke the program themselves.

    The schedule keeps its BOOKS as plain numbers a device (dispatches
    and rows issued, seconds the host spent in the feeds' `device_put`,
    bytes of the feeds that changed device or came from the host) and
    hands them over ONCE a call (`flush`): nothing is counted, gauged or
    locked a block beyond the one locked section `_note_dispatch` has.

    A HOME plan (``home``: the slot of the one device that holds every
    feed column, module docstring) has every non-empty item on that
    slot. Its runs of equal blocks go out as one group on the columns
    themselves (`at_home` says which, `note_run` books one), and its
    first `flush` counts the plan: ``scheduler.home_plans`` 1,
    ``scheduler.home_blocks`` the items it placed."""

    __slots__ = (
        "devices", "labels", "assignment", "executor", "weights",
        "_issued", "_remaining", "_lock", "_rows_known", "_books",
        "_handed", "_dirty", "_home", "_home_blocks",
    )

    def __init__(self, devices: Tuple, assignment: List[Optional[int]],
                 executor=None, weights: Optional[Sequence[int]] = None,
                 home: Optional[int] = None):
        self.devices = tuple(devices)
        self.labels = tuple(device_label(d) for d in self.devices)
        self.assignment = list(assignment)
        self.executor = executor
        # per-item weights (row counts): what `evict` re-places by.
        # Callers constructing BlockSchedule directly (tests) may omit
        # them — failover then re-places with unit weights.
        self.weights = (
            [1 if s is not None else 0 for s in self.assignment]
            if weights is None
            else [int(w) for w in weights]
        )
        # every caller that gives weights plans by rows (`schedule_for`:
        # the blocks' sizes); without them no row count is known
        self._rows_known = weights is not None
        self._issued = [False] * len(self.assignment)
        self._remaining = [0] * len(self.devices)
        for s in self.assignment:
            if s is not None:
                self._remaining[s] += 1
        self._lock = threading.Lock()
        # the books, a list a device each: dispatches, rows, put
        # seconds, bytes in; `_handed` is what `flush` last handed over
        self._books = [[0] * self.ndev, [0] * self.ndev,
                       [0.0] * self.ndev, [0] * self.ndev]
        self._handed = [list(b) for b in self._books]
        # a schedule that never dispatched still has its plan to show
        self._dirty = True
        self._home = home
        # what the first flush counts as a home plan's blocks, then None
        self._home_blocks = (
            None if home is None
            else sum(s is not None for s in self.assignment)
        )

    @property
    def ndev(self) -> int:
        return len(self.devices)

    def slot(self, i: int) -> Optional[int]:
        return self.assignment[i]

    def device(self, i: int):
        s = self.assignment[i]
        return None if s is None else self.devices[s]

    def label(self, i: int) -> Optional[str]:
        s = self.assignment[i]
        return None if s is None else self.labels[s]

    def anchor_device(self):
        """Where cross-device results converge (final combines, gathered
        partials): slot 0, deterministically."""
        return self.devices[0]

    # -- dispatch ------------------------------------------------------
    def put(self, i: int, feeds: Sequence) -> List:
        """The feeds on item ``i``'s device (`_place_feeds`: async) and
        the dispatch entered in the books."""
        s = self.assignment[i]
        if s is None:
            return list(feeds)
        out, secs, moved = _place_feeds(feeds, self.devices[s])
        self._note_dispatch((i,), s, secs, moved)
        # put-path verbs (reduce_rows folds, chunked aggregation) are
        # the only dispatches some workloads ever issue — a successful
        # transfer onto the device must close its half-open circuit
        # too, or a probe could hang in half-open forever
        _health.mark_success(self.labels[s])
        return out

    def bind(self, i: int, fn, valid=None):
        """The dispatch callable for item ``i``: feeds -> outputs on the
        assigned device. ``valid`` prefixes the call with the traced
        true-row-count scalar of a masked bucketed reduce program
        (`shape_policy.build_masked_reduce`'s calling convention).
        Detects per-device jit compiles by watching the program's jit
        cache across the call (best-effort under concurrent verbs —
        same caveat as `Executor._instrument`). The slot is read at
        CALL time, so a thunk rebuilt after `evict` re-placed the item
        dispatches to the item's NEW device; a successful call feeds
        the device-health registry (closes a half-open circuit)."""

        def call(*feeds):
            s = self.assignment[i]
            if s is None:
                return fn(*feeds) if valid is None else fn(
                    np.int32(valid), *feeds
                )
            put, secs, moved = _place_feeds(feeds, self.devices[s])
            sizer = getattr(fn, "_cache_size", None)
            n0 = None
            if callable(sizer):
                try:
                    n0 = sizer()
                except Exception:
                    n0 = None
            if valid is None:
                out = fn(*put)
            else:
                out = fn(np.int32(valid), *put)
            if n0 is not None:
                try:
                    n1 = sizer()
                except Exception:
                    n1 = None
                if n1 is not None and n1 > n0:
                    _bump(self.executor, "device_compiles",
                          self.labels[s], n1 - n0)
            self._note_dispatch((i,), s, secs, moved)
            _health.mark_success(self.labels[s])
            return out

        return call

    def progress(self) -> Dict[str, int]:
        """Partial-work accounting: how many planned dispatches have
        been issued vs not. What a `DeadlineExceeded` escaping a
        scheduled verb is stamped with (``tfs_blocks_issued`` /
        ``tfs_blocks_unissued``) — a cancelled verb stops issuing at
        the next boundary check, and this says exactly how far it
        got."""
        with self._lock:
            planned = sum(1 for s in self.assignment if s is not None)
            issued = sum(
                1
                for i, s in enumerate(self.assignment)
                if s is not None and self._issued[i]
            )
        return {
            "planned": planned,
            "issued": issued,
            "unissued": planned - issued,
        }

    def evict(self, index: int) -> Optional[str]:
        """Failover after a transient failure of item ``index``: open
        the circuit of its device (`DeviceHealth.mark_failure`) and
        re-place every not-yet-issued item — including ``index``
        itself — LPT onto the remaining usable devices, on top of the
        load the already-issued items put there. Already-computed
        partials stay where they are (their buffers are assumed
        readable — a HARD device loss surfaces at the combine and
        fails the verb after the budget). Returns the evicted device's
        label, or None when the item was unscheduled or no other
        usable device exists — in which case NOTHING is counted or
        circuit-opened: the retry re-runs in place, and an "eviction"
        with nowhere to go would overcount the re-placement metric
        (and, on a single-device schedule, open the only circuit)."""
        s = self.assignment[index]
        if s is None:
            return None
        label = self.labels[s]
        with self._lock:
            alive = [
                t for t in range(self.ndev)
                if t != s and _health.usable(self.labels[t])
            ]
        if not alive:
            return None
        _health.mark_failure(label)
        with self._lock:
            load = {t: 0 for t in alive}
            pending: List[int] = []
            for i, slot in enumerate(self.assignment):
                if slot is None:
                    continue
                if self._issued[i] and i != index:
                    if slot in load:
                        load[slot] += self.weights[i]
                else:
                    pending.append(i)
            # LPT over the survivors: heaviest pending item first onto
            # the least-loaded usable slot — same policy, same
            # determinism, as the original plan()
            pending.sort(key=lambda i: (-self.weights[i], i))
            for i in pending:
                t = min(alive, key=lambda a: (load[a], a))
                load[t] += max(1, self.weights[i])
                self.assignment[i] = t
            # rebuild the queue-depth ledger from the new assignment
            self._remaining = [0] * self.ndev
            for i, slot in enumerate(self.assignment):
                if slot is not None and not self._issued[i]:
                    self._remaining[slot] += 1
        return label

    def at_home(self, first: int, end: int, device) -> bool:
        """Whether this is a home plan on ``device`` that still has
        every item of ``[first, end)`` there (an `evict` may have
        re-placed some): the run may then go out as one dispatch on the
        columns themselves (`note_run`)."""
        home = self._home
        return (
            home is not None
            and self.devices[home] == device
            and all(s is None or s == home
                    for s in self.assignment[first:end])
        )

    def note_run(self, first: int, end: int) -> None:
        """The items of ``[first, end)`` went out as ONE dispatch on the
        home device, on the columns where they are: nothing was put."""
        self._note_dispatch(
            [i for i in range(first, end) if self.assignment[i] is not None],
            self._home, 0.0, 0,
        )
        _health.mark_success(self.labels[self._home])

    def _note_dispatch(
        self, items: Sequence[int], s: int, put_seconds: float, bytes_in: int
    ) -> None:
        """``items`` went out to slot ``s`` in one dispatch: into the
        books, under the one lock a dispatch takes. The plan's last
        dispatch hands the books over (`flush`)."""
        dispatches, rows, seconds, moved = self._books
        with self._lock:
            for i in items:
                if not self._issued[i]:
                    # a block split after running out of memory goes out
                    # twice: its rows are booked once
                    self._issued[i] = True
                    if self._rows_known:
                        rows[s] += self.weights[i]
                self._remaining[s] = max(0, self._remaining[s] - 1)
            dispatches[s] += 1
            seconds[s] += put_seconds
            moved[s] += bytes_in
            self._dirty = True
            done = not any(self._remaining)
        if done:
            self.flush()

    def flush(self) -> None:
        """Hand the books over, once a call: to the counters
        ``scheduler.dispatches`` / ``scheduler.rows`` /
        ``scheduler.put_seconds`` / ``scheduler.bytes_in`` (each
        ``{device=}``) and the executor's ``device_dispatches`` ledger
        what was entered since the last flush, and set the gauge
        ``scheduler_queue_depth{device=}`` to the planned dispatches
        that never went out (0 after a whole call); a home plan's first
        flush also counts it (``scheduler.home_plans``,
        ``scheduler.home_blocks``). Runs by itself when
        the plan's last dispatch is issued; a caller whose call may end
        early (`api._run_blocks`) calls it in a ``finally``. With
        nothing entered since the last flush it hands over nothing."""
        from ..utils import telemetry as _tele

        with self._lock:
            if not self._dirty:
                return
            self._dirty = False
            now = [list(b) for b in self._books]
            was, self._handed = self._handed, now
            depth = list(self._remaining)
            home_blocks, self._home_blocks = self._home_blocks, None
        if home_blocks is not None:
            _tele.counter_inc("scheduler.home_plans", 1.0)
            _tele.counter_inc("scheduler.home_blocks", float(home_blocks))
        gauge = _tele.enabled()
        names = ("scheduler.dispatches", "scheduler.rows",
                 "scheduler.put_seconds", "scheduler.bytes_in")
        for s, label in enumerate(self.labels):
            since = [new[s] - old[s] for new, old in zip(now, was)]
            # a device that was planned nothing reads 0, not nothing:
            # what a balance over the devices has to see
            for name, d in zip(names, since):
                _tele.counter_inc(name, float(d), device=label)
            if since[0]:
                _bump(self.executor, "device_dispatches", label, since[0])
            if gauge:
                _tele.gauge_set(
                    "scheduler_queue_depth", depth[s], device=label
                )


def _place_feeds(feeds: Sequence, dev) -> Tuple[List, float, int]:
    """``feeds`` on ``dev`` (`jax.device_put`: async), the seconds the
    host spent in the puts, and the bytes that changed device or came
    from the host (a numpy feed counts whole). An array already
    committed to ``dev`` is passed on as it is: no put, no seconds. A
    bound tree's leaves were placed, and counted, before the loop
    (`runtime.bindings`): its put finds them there and books nothing."""
    import jax

    out: List = []
    moved = 0
    t0 = None
    for f in feeds:
        here = None  # whether an array feed lives on `dev` alone
        if isinstance(f, jax.Array):
            here = f.devices() == {dev}
            if here and f.committed:
                out.append(f)
                continue
        if t0 is None:
            t0 = time.perf_counter()
        if not here:
            moved += getattr(f, "nbytes", 0)
        out.append(jax.device_put(f, dev))
    return out, (0.0 if t0 is None else time.perf_counter() - t0), moved


def _bump(ex, attr: str, label: str, n: int) -> None:
    """Increment a per-device ledger dict on the executor, under its
    lock when it has one. Executors without the ledger (stubs, native)
    are silently skipped — the ledgers are observability, not
    correctness."""
    d = getattr(ex, attr, None)
    if d is None:
        return
    lock = getattr(ex, "_lock", None)
    if lock is not None:
        with lock:
            d[label] = d.get(label, 0) + n
    else:  # pragma: no cover - executors always carry _lock today
        d[label] = d.get(label, 0) + n


def schedule_weights(
    weights: Sequence[int], devices=None, executor=None, mesh=None,
    home=None,
) -> Optional[BlockSchedule]:
    """Resolve the device set and plan ``weights`` over it; None when
    scheduling is off for this dispatch (the caller then runs the
    ordinary unscheduled loop). ``home`` is the one device that holds
    every feed column of a row-local map (None: there is none, or the
    caller's program may be worth moving): where the set is the
    scheduler's own choice and holds it, every item with rows is planned
    there (the home plan, module docstring); else LPT over the rows."""
    devs = resolve(devices=devices, executor=executor, mesh=mesh)
    if devs is None:
        return None
    if home is not None and devices is None and home in devs:
        slot = devs.index(home)
        return BlockSchedule(
            devs, [slot if int(w) > 0 else None for w in weights],
            executor=executor, weights=weights, home=slot,
        )
    return BlockSchedule(
        devs, plan(weights, len(devs)), executor=executor, weights=weights
    )


def schedule_for(
    frame, devices=None, executor=None, mesh=None, home=None
) -> Optional[BlockSchedule]:
    """`schedule_weights` over a frame's block sizes — the per-block
    verbs' entry point (one dispatch per non-empty block, weighted by
    row count)."""
    return schedule_weights(
        frame.block_sizes(), devices=devices, executor=executor, mesh=mesh,
        home=home,
    )
