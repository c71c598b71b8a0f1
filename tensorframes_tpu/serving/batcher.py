"""Cross-request micro-batching: many small requests, one bucketed dispatch.

A serving workload is thousands of concurrent SMALL evaluations of the
same few programs — dispatch overhead per request (Python verb entry,
jit call, H2D) dwarfs the per-row compute the way per-row session.run
dwarfed it in the reference. This module coalesces concurrent requests
per ``(endpoint, program fingerprint)`` into ONE dispatch, the serving
analogue of the ingest engine's stage overlap:

- Requests queue into a per-key **lane**; the lane's dispatcher thread
  holds an open batch for ``config.serve_batch_window_ms``, closing
  EARLY the moment the row total lands exactly on a bucket-ladder rung
  (padding waste zero — waiting longer could only push the batch into
  the next rung) or reaches ``max_batch_rows``. A single oversized
  request dispatches alone.
- The closed batch concatenates request rows, pads to the rung with
  `shape_policy.pad_lead` (so the dispatch shape is ALWAYS a warmed
  rung, independent of the global ``shape_bucketing`` knob), runs the
  endpoint's program through the ordinary verb path — block scheduler
  placement, admission control (the coalesced dispatch takes ONE
  admission slot: batching composes with, not around, the PR 9 gate),
  fault handling — and scatters per-request row slices back through
  `concurrent.futures.Future`s.
- **Bit-identity**: the registry only marks endpoints batchable when
  the shared row-local walk proves every fetch row-local, so output
  row i is a function of input row i alone — concat + dispatch + slice
  is bit-identical to per-request execution by construction
  (tests/test_serving.py asserts it against direct verb calls).

Overload: a lane whose queue exceeds ``config.serve_queue_limit``
sheds new arrivals immediately with the same typed `OverloadError` the
admission controller uses (retry-after derived from the live
``verb_seconds`` histogram) — the HTTP front-end maps it to 429 +
``Retry-After``. Deadlines: each queued request carries its caller's
ambient absolute deadline; the batch runs under the LOOSEST member
budget (a tight-budget member that cannot wait raises its own
`DeadlineExceeded` at the waiter, never dragging batch-mates down),
and a waiter that gives up cancels its future so an unstarted request
is dropped instead of computed for nobody.

Telemetry (always-live): ``serve_requests{endpoint=}`` /
``serve_batches{endpoint=}`` / ``serve_shed{endpoint=}`` counters,
``serve_batch_rows`` / ``serve_batch_fill`` / ``serve_queue_seconds``
histograms, registered ``serve_pending`` gauge.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..frame import Column, TensorFrame
from ..runtime import deadline as _dl

__all__ = ["MicroBatcher", "batcher"]


def _window_s(endpoint, cfg) -> float:
    """Effective coalescing window for one endpoint, in seconds: the
    endpoint's own ``batch_window_ms`` (the autotuner's per-endpoint
    override) when set, else the global config knob. Read per batch,
    so a tuned change applies to the next window immediately — and an
    operator PIN of the global knob (explicit update/override/env)
    wins over any previously tuned endpoint value at read time, so
    pinning after the tuner ran still takes effect everywhere."""
    w = getattr(endpoint, "batch_window_ms", None)
    if w is None or _window_pinned():
        w = float(getattr(cfg, "serve_batch_window_ms", 0.0))
    return float(w) / 1e3


def _window_pinned() -> bool:
    try:
        from .. import config as _config

        return _config.is_explicit("serve_batch_window_ms")
    except Exception:
        return False


class _Request:
    __slots__ = (
        "frame", "rows", "future", "request_id", "deadline_at", "t_enq",
    )

    def __init__(self, frame, rows, future, request_id, deadline_at):
        self.frame = frame
        self.rows = rows
        self.future = future
        self.request_id = request_id
        self.deadline_at = deadline_at  # absolute monotonic, or None
        self.t_enq = time.monotonic()


class _Lane:
    """One (endpoint, program) batching lane: a bounded queue drained by
    a dedicated daemon dispatcher thread."""

    def __init__(self, key: Tuple[str, str], endpoint):
        self.key = key
        self.endpoint = endpoint
        self.lock = threading.Lock()
        self.cond = threading.Condition(self.lock)
        self.queue: deque = deque()
        self.stop = False
        self.thread: Optional[threading.Thread] = None

    def depth(self) -> int:
        return len(self.queue)  # GIL-atomic len; gauge read, see deadline


class MicroBatcher:
    """Process-wide batcher: one lane per (endpoint, fingerprint)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._lanes: Dict[Tuple[str, str], _Lane] = {}
        # accounting (under self._lock)
        self.requests = 0
        self.batches = 0
        self.batched_requests = 0
        self.inline = 0
        self.shed = 0

    # -- introspection --------------------------------------------------
    def pending(self) -> int:
        with self._lock:
            lanes = list(self._lanes.values())
        return sum(lane.depth() for lane in lanes)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "lanes": len(self._lanes),
                "pending": sum(l.depth() for l in self._lanes.values()),
                "requests": self.requests,
                "batches": self.batches,
                "batched_requests": self.batched_requests,
                "inline": self.inline,
                "shed": self.shed,
            }

    # -- the entry point ------------------------------------------------
    def submit(
        self,
        endpoint,
        frame: TensorFrame,
        request_id: Optional[str] = None,
        validate: bool = True,
    ) -> Future:
        """Queue one request; returns a Future resolving to the
        endpoint's outputs-only response frame. Validation errors and
        lane overload raise synchronously (the caller maps them to
        400 / 429); execution errors surface through the future.

        The caller's ambient deadline (`runtime.deadline`) rides along:
        it bounds the batch dispatch and the caller's own wait."""
        from ..utils import telemetry as _tele

        if validate:
            endpoint.validate_request(frame)
        with self._lock:
            self.requests += 1
        _tele.counter_inc("serve_requests", 1.0, endpoint=endpoint.name)

        from .. import config as _config

        cfg = _config.get()
        window_s = _window_s(endpoint, cfg)
        fut: Future = Future()

        if not endpoint.batchable or window_s <= 0.0:
            # unbatched: run inline on the caller's thread, under the
            # caller's own scope — one request, one dispatch, one slot
            with self._lock:
                self.inline += 1
            if not fut.set_running_or_notify_cancel():
                return fut
            try:
                fut.set_result(endpoint.run_frame(frame))
            except BaseException as e:
                fut.set_exception(e)
            return fut

        scope = _dl.current_scope()
        deadline_at = None
        if scope is not None and scope.deadline is not None:
            deadline_at = scope.deadline.at
        req = _Request(frame, frame.nrows, fut, request_id, deadline_at)

        qlimit = int(getattr(cfg, "serve_queue_limit", 0) or 0)
        while True:
            lane = self._lane(endpoint)
            with lane.cond:
                if lane.stop:
                    # lost a race with drop()/shutdown(): the dispatcher
                    # may already have drained and exited — an append
                    # here would never resolve. Re-fetch; _lane() makes
                    # a fresh lane for a stopped one.
                    continue
                if qlimit > 0 and len(lane.queue) >= qlimit:
                    with self._lock:
                        self.shed += 1
                    _tele.counter_inc(
                        "serve_shed", 1.0, endpoint=endpoint.name
                    )
                    depth = len(lane.queue)
                    mean = _dl._mean_verb_seconds()
                    retry_after = max(0.001, (mean or 0.05) * (depth + 1))
                    raise _dl.OverloadError(
                        f"endpoint {endpoint.name!r}: batching lane full "
                        f"— {depth} request(s) queued (limit {qlimit}); "
                        f"retry in ~{retry_after:.3f}s",
                        queue_depth=depth, limit=qlimit,
                        retry_after_s=retry_after,
                    )
                lane.queue.append(req)
                lane.cond.notify()
            return fut

    # -- lanes ----------------------------------------------------------
    def _lane(self, endpoint) -> _Lane:
        key = (endpoint.name, endpoint.fingerprint)
        with self._lock:
            lane = self._lanes.get(key)
            if lane is None or lane.stop:
                lane = _Lane(key, endpoint)
                lane.thread = threading.Thread(
                    target=self._run_lane,
                    args=(lane,),
                    daemon=True,
                    name=f"tfs-serve-{endpoint.name}",
                )
                self._lanes[key] = lane
                lane.thread.start()
            return lane

    def drop(self, endpoint_name: str) -> None:
        """Stop every lane of one endpoint (unregister / replace):
        queued requests still dispatch — the lane drains before its
        thread exits."""
        with self._lock:
            doomed = [
                lane for key, lane in self._lanes.items()
                if key[0] == endpoint_name
            ]
            for lane in doomed:
                self._lanes.pop(lane.key, None)
        for lane in doomed:
            with lane.cond:
                lane.stop = True
                lane.cond.notify_all()
            lane.thread.join(timeout=30.0)

    def shutdown(self) -> None:
        """Stop every lane (tests / process teardown). Queued requests
        drain through one final dispatch per lane."""
        with self._lock:
            lanes = list(self._lanes.values())
            self._lanes.clear()
            self.requests = self.batches = 0
            self.batched_requests = self.inline = self.shed = 0
        for lane in lanes:
            with lane.cond:
                lane.stop = True
                lane.cond.notify_all()
        for lane in lanes:
            lane.thread.join(timeout=30.0)

    # -- the dispatcher -------------------------------------------------
    def _run_lane(self, lane: _Lane) -> None:
        from .. import config as _config
        from .. import shape_policy as _sp

        ep = lane.endpoint
        while True:
            with lane.cond:
                while not lane.queue and not lane.stop:
                    lane.cond.wait(0.25)
                if not lane.queue and lane.stop:
                    return
                cfg = _config.get()
                window_s = _window_s(ep, cfg)
                max_rows = ep.max_batch_rows
                t_close = time.monotonic() + window_s
                batch: List[_Request] = []
                rows = 0
                while True:
                    while lane.queue:
                        r = lane.queue[0]
                        if batch and rows + r.rows > max_rows:
                            break  # r starts the NEXT batch
                        lane.queue.popleft()
                        batch.append(r)
                        rows += r.rows
                    if rows >= max_rows:
                        break
                    # rung-fill early close: exactly on a ladder rung,
                    # more coalescing could only cost the next rung
                    if rows and rows == _sp.bucket_for(rows):
                        break
                    if lane.stop:
                        break
                    left = t_close - time.monotonic()
                    if left <= 0.0:
                        break
                    lane.cond.wait(left)
            if batch:
                self._dispatch(ep, batch)

    def _dispatch(self, ep, batch: List[_Request]) -> None:
        from .. import shape_policy as _sp
        from ..utils import telemetry as _tele

        now = time.monotonic()
        # claim the futures at dispatch time (not enqueue): a waiter
        # whose deadline expired while queued has cancel()led — drop it
        # here instead of computing rows nobody will read
        live = [r for r in batch if r.future.set_running_or_notify_cancel()]
        if not live:
            return
        for r in live:
            _tele.histogram_observe(
                "serve_queue_seconds", max(0.0, now - r.t_enq)
            )
        rows = sum(r.rows for r in live)
        # the batch runs under the LOOSEST member budget: a member with
        # a tighter one gives up at its own waiter without dragging its
        # batch-mates down; any unbounded member leaves the batch on
        # the config default (verb entry still applies it)
        timeout_s = None
        deadlines = [r.deadline_at for r in live]
        if all(d is not None for d in deadlines):
            timeout_s = max(0.001, max(deadlines) - now)
        try:
            # ONE single-block frame of exactly the program's columns —
            # whatever block structure the requests arrived with, the
            # coalesced dispatch is one block on one warmed shape
            cols = []
            for c in ep.required_columns:
                parts = [
                    np.asarray(r.frame.column(c).values) for r in live
                ]
                cols.append(
                    Column(
                        c,
                        parts[0] if len(parts) == 1
                        else np.concatenate(parts),
                    )
                )
            base = TensorFrame(cols, offsets=[0, rows])
            # pad to the rung OURSELVES (replicated last row, the
            # numerically-ordinary pad `shape_policy` documents) so the
            # dispatch shape is a warmed rung regardless of the global
            # shape_bucketing knob; the pad tail is sliced off with the
            # scatter below
            rung = _sp.bucket_for(rows)
            # serving's own bucket-economics signal: fill fraction of
            # the rung this coalesced batch pads to, labeled per
            # endpoint (the batch-window autotuner reads it next to
            # serve_batch_rows/serve_queue_seconds). Observed ONLY for
            # rung-shaped dispatches — an oversized request dispatches
            # unpadded at its exact shape, so there is no rung fill to
            # report (the inner verb's own pad accounting covers it).
            # NB the padded frame below dispatches exactly on a rung,
            # so the inner verb records fill=1.0 under its OWN label —
            # true by construction: serving absorbs the pad waste here
            # and the map-level dispatch genuinely wastes nothing.
            if rows == rung or (rung > rows and rows <= ep.max_batch_rows):
                _sp.observe_fill(rows, rung, verb=f"serve:{ep.name}")
            if rung > rows and rows <= ep.max_batch_rows:
                padded = TensorFrame(
                    [
                        Column(
                            c,
                            _sp.pad_lead(base.column(c).values, rows, rung),
                        )
                        for c in ep.required_columns
                    ],
                    offsets=[0, rung],
                )
            else:
                padded = base
            ids = ",".join(
                r.request_id for r in live if r.request_id
            ) or None
            ctx = (
                _tele.request_scope(ids) if ids is not None
                else _nullcontext()
            )
            with ctx:
                out = ep.run_frame(padded, timeout_s=timeout_s)
            with self._lock:
                self.batches += 1
                self.batched_requests += len(live)
            _tele.counter_inc("serve_batches", 1.0, endpoint=ep.name)
            _tele.histogram_observe("serve_batch_rows", float(rows))
            _tele.histogram_observe("serve_batch_fill", float(len(live)))
            # scatter: per-request row slices of every output column
            out_vals = [
                (name, out.column(name).values) for name in ep.output_names
            ]
            lo = 0
            for r in live:
                hi = lo + r.rows
                res = TensorFrame(
                    [Column(name, v[lo:hi]) for name, v in out_vals],
                    offsets=[0, r.rows],
                )
                lo = hi
                try:
                    r.future.set_result(res)
                except Exception:
                    pass  # waiter gone; nothing to tell
        except BaseException as e:  # typed errors flow to every member
            for r in live:
                try:
                    r.future.set_exception(e)
                except Exception:
                    pass  # cancelled waiter: the error has no audience


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


_batcher = MicroBatcher()


def batcher() -> MicroBatcher:
    """The process-wide micro-batcher."""
    return _batcher


# live pending-request gauge: registered like the admission gauges
# (evaluated at export, survives telemetry.reset())
def _register_gauge() -> None:
    try:
        from ..utils import telemetry as _tele

        _tele.gauge_register(
            "serve_pending", lambda: float(_batcher.pending())
        )
    except Exception:  # pragma: no cover - telemetry always importable
        pass


_register_gauge()
