#!/usr/bin/env python
"""Render (and diff) saved workload profiles offline.

Usage::

    python tools/profile_report.py PROFILE.json
    python tools/profile_report.py A.json --diff B.json [--fail-on-drift]
    python tools/profile_report.py PROFILE.json --json

A profile is what `tfs.profile.snapshot().save(path)` writes (also
scraped live from the telemetry server's ``/profile`` route). The report
renders the sections a tuning/capacity reader wants in one screen:
per-verb totals + latency quantile sketch, per-program exec/rung/cost
rows, bucket fill economics, serving batch economics, ingest
busy/starvation, admission pressure, and the cost-model residual flags.

``--diff`` compares two profiles with `WorkloadProfile.diff`:
STRUCTURAL drift (program/rung/verb/endpoint/stage identity changes)
prints separately from TIMING deltas, and ``--fail-on-drift`` exits 2
on structural drift — the CI hook for "same workload, same plan".
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

# script-invocation bootstrap (CI runs `python tools/profile_report.py`
# without installing the package): the repo root precedes tools/ on
# sys.path — same recipe as tools/endpoint_smoke.py
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _fmt_bytes(n) -> str:
    if n is None:
        return "?"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}TiB"


def _hist_quantile(h: Optional[Dict], q: float) -> Optional[float]:
    """Upper-bound quantile estimate from a fixed-bucket histogram
    (the bucket boundary the q-quantile falls under; +Inf reads as
    None — honest 'beyond the ladder')."""
    if not h or not h.get("count"):
        return None
    target = q * h["count"]
    cum = 0
    for b, c in zip(h["buckets"], h["counts"]):
        cum += c
        if cum >= target:
            return float(b)
    return None  # lives in the +Inf bucket


def _hist_mean(h: Optional[Dict]) -> Optional[float]:
    if not h or not h.get("count"):
        return None
    return h["sum"] / h["count"]


def render(data: Dict) -> str:
    lines: List[str] = []
    meta = data.get("meta", {})
    lines.append("workload profile")
    lines.append("=" * 16)
    lines.append(
        f"captured: host={meta.get('host')} pid={meta.get('pid')} "
        f"unix={meta.get('created_unix')} "
        f"devices={meta.get('device_count')}x{meta.get('device_kind')}"
        + (f" note={meta.get('note')!r}" if meta.get("note") else "")
    )
    verbs = data.get("verbs", {}) or {}
    if verbs and "error" not in verbs:
        lines.append("")
        lines.append("verbs:")
        for name, v in sorted(
            verbs.items(), key=lambda kv: -kv[1].get("seconds", 0.0)
        ):
            p50 = _hist_quantile(v.get("latency"), 0.5)
            p99 = _hist_quantile(v.get("latency"), 0.99)
            quant = ""
            if p50 is not None:
                quant = f"  p50<={p50:g}s p99<={p99 if p99 else float('inf'):g}s"
            lines.append(
                f"  {name:<28} calls={v.get('calls', 0):<5} "
                f"total={v.get('seconds', 0.0):.4f}s "
                f"rows={int(v.get('rows', 0))}{quant}"
            )
    progs = data.get("programs", {}) or {}
    if progs and "error" not in progs:
        lines.append("")
        lines.append("programs (cost ledger):")
        for fp, p in sorted(
            progs.items(), key=lambda kv: -kv[1].get("execs", 0)
        ):
            shapes = p.get("shapes", [])
            flops = next(
                (s["flops"] for s in shapes if s.get("flops")), None
            )
            by = next(
                (
                    s["bytes_accessed"]
                    for s in shapes
                    if s.get("bytes_accessed")
                ),
                None,
            )
            lines.append(
                f"  {fp:<16} execs={p.get('execs', 0):<6} "
                f"rungs={p.get('rungs', [])} "
                f"flops/exec={flops if flops is not None else '?'} "
                f"hbm/exec={_fmt_bytes(by)}"
            )
    bk = data.get("bucketing", {}) or {}
    if bk.get("padded_dispatches") or bk.get("fill"):
        lines.append("")
        lines.append(
            f"bucketing: padded_dispatches={bk.get('padded_dispatches', 0)} "
            f"pad_rows={bk.get('pad_rows', 0)}"
        )
        for verb, h in sorted((bk.get("fill") or {}).items()):
            m = _hist_mean(h)
            lines.append(
                f"  fill[{verb}]: mean="
                + (f"{m:.3f}" if m is not None else "?")
                + f" over {h.get('count', 0)} dispatch(es)"
            )
    sv = data.get("serving", {}) or {}
    if sv.get("endpoints"):
        lines.append("")
        lines.append("serving:")
        for name, e in sorted(sv["endpoints"].items()):
            lines.append(
                f"  {name:<20} requests={e.get('requests', 0)} "
                f"batches={e.get('batches', 0)} shed={e.get('shed', 0)}"
            )
        rows_m = _hist_mean(sv.get("batch_rows"))
        req_m = _hist_mean(sv.get("batch_requests"))
        q99 = _hist_quantile(sv.get("queue_seconds"), 0.99)
        lines.append(
            "  batches: mean_rows="
            + (f"{rows_m:.1f}" if rows_m is not None else "?")
            + " mean_coalesced="
            + (f"{req_m:.1f}" if req_m is not None else "?")
            + " queue_p99<="
            + (f"{q99:g}s" if q99 is not None else "?")
        )
    ing = data.get("ingest", {}) or {}
    if ing and "error" not in ing:
        lines.append("")
        lines.append("ingest (busy vs starved per stage):")
        for stage, s in sorted(ing.items()):
            busy, wait = s.get("busy_s", 0.0), s.get("wait_s", 0.0)
            tot = busy + wait
            frac = f" busy_frac={busy / tot:.2f}" if tot > 0 else ""
            lines.append(
                f"  {stage:<12} chunks={int(s.get('chunks', 0)):<6} "
                f"busy={busy:.4f}s starved={wait:.4f}s{frac}"
            )
    adm = data.get("admission", {}) or {}
    if "error" not in adm and (
        adm.get("admitted") or adm.get("shed") or adm.get("wait_seconds")
    ):
        lines.append("")
        lines.append(
            f"admission: admitted={adm.get('admitted', 0)} "
            f"shed={adm.get('shed', 0)} "
            f"peak_in_flight={adm.get('peak_in_flight', 0)} "
            f"queued_wait={adm.get('wait_seconds', 0.0):.4f}s"
        )
        for verb, n in sorted(
            (adm.get("deadline_exceeded") or {}).items()
        ):
            lines.append(f"  deadline_exceeded[{verb}]: {n}")
    res = data.get("residuals", {}) or {}
    if res.get("programs"):
        warn = res.get("warn_ratio")
        lines.append("")
        lines.append(
            f"cost-model residuals (flag threshold x{warn:g}):"
        )
        for fp, p in sorted(res["programs"].items()):
            r = p.get("residual_ratio")
            if r is None:
                continue
            flag = "  ** FLAGGED" if p.get("flagged") else ""
            lines.append(f"  {fp:<16} residual={r:.2f}x{flag}")
    return "\n".join(lines)


def render_diff(diff: Dict) -> str:
    lines: List[str] = []
    if diff["structural"]:
        lines.append(
            f"STRUCTURAL DRIFT ({len(diff['structural'])} item(s)) — "
            "these runs are not the same workload/plan:"
        )
        for s in diff["structural"]:
            lines.append(f"  {s}")
    else:
        lines.append(
            "structural drift: none (same programs, rungs, verbs, "
            "endpoints, stages)"
        )
    if diff["timing"]:
        lines.append(f"timing deltas ({len(diff['timing'])} item(s)):")
        for t in diff["timing"]:
            ratio = (
                f" ({t['ratio']:.2f}x)" if t.get("ratio") is not None else ""
            )
            lines.append(
                f"  {t['what']}: {t['a']:g} -> {t['b']:g}"
                f" (delta {t['delta']:+g}){ratio}"
            )
    else:
        lines.append("timing deltas: none")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("profile", help="saved WorkloadProfile JSON")
    ap.add_argument(
        "--diff", metavar="OTHER",
        help="second profile to compare against (A=profile, B=OTHER)",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="emit the payload (report or diff) as JSON",
    )
    ap.add_argument(
        "--fail-on-drift", action="store_true",
        help="with --diff: exit 2 when structural drift is detected",
    )
    args = ap.parse_args(argv)

    # imports deferred past argparse so --help never pays the jax import
    from tensorframes_tpu.runtime import profiler

    a = profiler.load(args.profile)
    if args.diff:
        d = a.diff(profiler.load(args.diff))
        print(json.dumps(d, indent=1) if args.json else render_diff(d))
        if args.fail_on_drift and d["structural_drift"]:
            return 2
        return 0
    if args.json:
        print(json.dumps(a.to_dict(), indent=1, sort_keys=True))
    else:
        print(render(a.to_dict()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
