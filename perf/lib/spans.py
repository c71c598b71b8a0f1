"""From the package's span ring to host milliseconds per verb call.

`per_call` takes nothing but a list of spans (the package's
`telemetry.Span`: span_id, parent_id, name, kind, t0, t1 on
`time.perf_counter`) and the traced slice's calls (`window.Call`:
`issued` / `returned` on the same clock), so a hand-written list tests it.
The sums by name and the self times are the package's own
(`telemetry.span_aggregates(...)["by_name"]`); a program that has no
such table gives `None`, and every reader then leaves its metric out.
"""

import bisect
import json


def per_call(spans, calls):
    """Per-call means over the calls whose spans are all still in the
    list: the root `verb` spans that start between a call's issue and its
    return, where the list's oldest span is older than that issue (the
    ring evicts oldest first, so nothing of a later call is gone), each
    with its whole subtree. Returns None where no such call is left."""
    from tensorframes_tpu.utils import telemetry

    calls = sorted((c for c in calls if c.returned is not None),
                   key=lambda c: c.issued)
    if not spans or not calls:
        return None
    oldest = min(s.t0 for s in spans)
    issued = [c.issued for c in calls]
    by_id = {s.span_id: s for s in spans}
    children = {}
    for s in spans:
        if s.parent_id in by_id:
            children.setdefault(s.parent_id, []).append(s)
    roots, read = [], {}
    for s in spans:
        if s.kind != "verb" or s.parent_id in by_id:
            continue
        i = bisect.bisect_right(issued, s.t0) - 1
        if i >= 0 and oldest < calls[i].issued and s.t0 <= calls[i].returned:
            roots.append(s)
            read[i] = calls[i]
    if not roots:
        return None
    subset, stack = [], list(roots)
    while stack:
        s = stack.pop()
        subset.append(s)
        stack.extend(children.get(s.span_id, ()))
    by_name = telemetry.span_aggregates(subset).get("by_name")
    if by_name is None:
        return None
    n = len(read)
    verbs = {s.name for s in roots}

    def ms(names, key="seconds"):
        return 1e3 * sum(by_name[x][key] for x in set(names) if x in by_name) / n

    verb_ms = ms(verbs)
    return {
        "calls": n,
        "verb_ms": verb_ms,
        # the same calls on the benchmark's clock, issue to return
        "clock_ms": 1e3 * sum(c.returned - c.issued for c in read.values()) / n,
        "plan_ms": ms(v + ".plan" for v in verbs),
        "pad_ms": ms(("shape.pad", "shape.unpad")),
        "dispatch_ms": ms(s.name for s in subset if s.kind == "dispatch")
        + ms((v + ".blocks" for v in verbs), "self_seconds"),
        "cut_concat_ms": ms(("frame.cut", "frame.concat")),
        "unattributed_pct": 100.0 * ms(verbs, "self_seconds") / verb_ms
        if verb_ms else None,
        "by_name": {
            k: {"per_call": v["count"] / n, "ms": 1e3 * v["seconds"] / n,
                "self_ms": 1e3 * v["self_seconds"] / n}
            for k, v in by_name.items()
        },
    }


def metric(ctx, key):
    """What a reader returns: `key` of `per_call` over the ring as it
    stands after the traced slice, computed once a run and printed once
    (a line before the result line), or None."""
    if not hasattr(ctx, "spans_per_call"):
        from tensorframes_tpu.utils import telemetry

        ctx.spans_per_call = per_call(telemetry.spans(), ctx.traced_calls)
        if ctx.spans_per_call is not None:
            print(json.dumps({"spans_per_call": ctx.spans_per_call}), flush=True)
    got = ctx.spans_per_call
    return None if got is None else got[key]
