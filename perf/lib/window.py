"""The closed loop that every cell's window runs, and the end-to-end
arithmetic over what it recorded."""

import collections
import math
import time


class Call:
    __slots__ = ("issued", "returned", "ready", "error")

    def __init__(self, issued):
        self.issued = issued
        self.returned = None  # the verb call came back (work enqueued)
        self.ready = None     # the caller saw its outputs ready
        self.error = None


def closed_loop(issue, wait, seconds, in_flight=2, max_calls=None,
                clock=time.perf_counter):
    """Issue calls for `seconds` with at most `in_flight` outstanding:
    after issuing call k the loop waits for call k - in_flight + 1. With
    `max_calls` it stops after that many (the warm-up). A
    call is timed from its issue to the caller seeing it ready. Returns
    (start, end, calls); `end` is when the last call issued inside the
    window was ready. A call that raises ends the loop."""
    calls, pending = [], collections.deque()

    def drain(keep):
        while len(pending) > keep:
            call, out = pending.popleft()
            try:
                wait(out)
            except Exception as e:  # the call failed on the device
                call.error = repr(e)
            call.ready = clock()

    start = clock()
    deadline = start + seconds
    while True:
        now = clock()
        if now >= deadline or (max_calls is not None and len(calls) >= max_calls):
            break
        call = Call(now)
        calls.append(call)
        try:
            out = issue()
        except Exception as e:
            call.error = repr(e)
            call.returned = call.ready = clock()
            break
        call.returned = clock()
        pending.append((call, out))
        del out
        drain(in_flight - 1)
    drain(0)
    return start, clock(), calls


def percentile(values, q):
    """Nearest rank: the smallest value with at least q of the sample at
    or below it."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def summarize(start, end, calls, rows_per_call):
    done = [c for c in calls if c.error is None]
    lat = [c.ready - c.issued for c in done]
    half = percentile(lat, 0.50) if lat else None
    return {
        "seconds": end - start,
        "attempted": len(calls),
        "raised": len(calls) - len(done),
        "rows": rows_per_call * len(done),
        "rows_per_s": rows_per_call * len(done) / (end - start),
        "call_p95_ms": 1e3 * percentile(lat, 0.95) if lat else None,
        "call_p50_ms": 1e3 * half if lat else None,
        "call_max_ms": 1e3 * max(lat) if lat else None,
        # [seconds into the window, ms] of calls over 1.5 medians: where a
        # run that completed fewer calls than its neighbours lost them
        "slow_calls": [
            [c.issued - start, 1e3 * (c.ready - c.issued)]
            for c in done if c.ready - c.issued > 1.5 * half
        ][:8],
        "verb_host_ms_per_call": (
            1e3 * sum(c.returned - c.issued for c in done) / len(done)
            if done else None
        ),
    }
