"""From the profiler's trace to numbers, in two steps.

1. `load_events`: xplane file -> flat list of events (plane, line, name,
   start, duration), seconds on the trace's own clock.
2. `reduce_events`: that list -> busy and idle share per device, device
   time per XLA module, the device operations that took most time, and
   the idle gaps named by what the host was doing in them.

Step 2 takes nothing but the list, so a hand-written list tests it.
"""

import bisect
import collections
import glob
import os
import re

Event = collections.namedtuple("Event", "plane line name start dur")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARKS = ("perf.issue", "perf.wait")  # the benchmark's own annotations


def find_xplane(trace_dir):
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                events.append(
                    Event(plane.name, line.name, ev.name,
                          ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                )
    return events


def op_label(name):
    """`%add_fusion = f32[268435456]{0:T(1024)} fusion(...)` ->
    `add_fusion f32[268435456]`: the operation and what it writes."""
    m = re.match(r"^%?([^\s=]+) = (\(?[^\s{(]+)", name)
    if not m:
        return name[:120]
    wrote = m.group(2)
    return f"{m.group(1)} {wrote + ', ...)' if wrote.startswith('(') else wrote}"


def module_base(name):
    """`jit_fn(123456789)` -> `jit_fn`."""
    return re.sub(r"\(\d+\)$", "", name)


def _union(intervals):
    """Merged, sorted (start, end) list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(ev, lo, hi):
    s, e = max(ev.start, lo), min(ev.start + ev.dur, hi)
    return (s, e) if e > s else None


def innermost_segments(host):
    """One host thread's events, which nest, flattened to (start, end,
    name) pieces that name the innermost event open at each moment."""
    segs, stack = [], []  # stack of (end, name)
    cur = None

    def emit(a, b, name):
        if b > a:
            segs.append((a, b, name))

    for ev in sorted(host, key=lambda e: (e.start, -e.dur)):
        if cur is None:
            cur = ev.start
        while stack and stack[-1][0] <= ev.start:
            end, name = stack.pop()
            emit(cur, end, name)
            cur = max(cur, end)
        if stack:
            emit(cur, ev.start, stack[-1][1])
        cur = max(cur, ev.start)
        stack.append((ev.start + ev.dur, ev.name))
    while stack:
        end, name = stack.pop()
        emit(cur, end, name)
        cur = max(cur, end)
    return segs


def _name_gap(segs, seg_starts, a, b, into):
    """Share the idle gap [a, b) among the host pieces under it."""
    i = max(0, bisect.bisect_right(seg_starts, a) - 1)
    covered = 0.0
    while i < len(segs) and segs[i][0] < b:
        s, e, name = segs[i]
        o = min(e, b) - max(s, a)
        if o > 0:
            into[name] += o
            covered += o
        i += 1
    if b - a - covered > 1e-12:
        into["(no host event)"] += b - a - covered


def reduce_events(events, program_pattern, top=10):
    """See the module docstring. Returns None where the list holds no
    device plane or none of the benchmark's marks."""
    program = re.compile(program_pattern)
    marks = [e for e in events if e.name in MARKS]
    devices = sorted(
        {e.plane for e in events if DEVICE_PLANE.match(e.plane)},
        key=lambda p: int(DEVICE_PLANE.match(p).group(1)),
    )
    if not marks or not devices:
        return None
    lo = min(e.start for e in marks)
    hi = max(e.start + e.dur for e in marks)
    # the host thread that issues: where the marks are
    mark_line = (marks[0].plane, marks[0].line)
    segs = innermost_segments(
        [e for e in events if (e.plane, e.line) == mark_line]
    )
    seg_starts = [s[0] for s in segs]

    per_device, op_seconds, module_seconds = [], collections.Counter(), collections.Counter()
    gap_seconds = collections.Counter()
    by_plane = collections.defaultdict(list)
    for e in events:
        if e.line in (OPS_LINE, MODULES_LINE):
            by_plane[e.plane].append(e)
    for plane in devices:
        busy_iv = []
        for e in by_plane[plane]:
            if e.line == OPS_LINE:
                c = _clip(e, lo, hi)
                if c:
                    busy_iv.append(c)
                    op_seconds[op_label(e.name)] += c[1] - c[0]
            else:
                c = _clip(e, lo, hi)
                if c:
                    module_seconds[module_base(e.name)] += c[1] - c[0]
        merged = _union(busy_iv)
        busy = sum(e - s for s, e in merged)
        per_device.append({"plane": plane, "busy_s": busy,
                           "idle_pct": 100.0 * (1.0 - busy / (hi - lo))})
        edge = lo
        for s, e in merged + [[hi, hi]]:
            if s > edge:
                _name_gap(segs, seg_starts, edge, s, gap_seconds)
            edge = max(edge, e)

    total_mod = sum(module_seconds.values())
    prog = sum(v for k, v in module_seconds.items() if program.search(k))
    return {
        "window_s": hi - lo,
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "per_device": per_device,
        "module_seconds": dict(module_seconds),
        "program_seconds": prog,
        "other_module_seconds": total_mod - prog,
        "marks": {m: sum(1 for e in marks if e.name == m) for m in MARKS},
        "device_ops": [[k, v] for k, v in op_seconds.most_common(top)],
        "idle_gaps": [[k, v] for k, v in gap_seconds.most_common(top)],
    }

