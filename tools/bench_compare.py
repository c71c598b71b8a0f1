#!/usr/bin/env python
"""Diff a benchmark run against a recorded baseline, with tolerances.

Usage::

    python benchmarks/run_all.py | tee /tmp/bench.jsonl
    python tools/bench_compare.py /tmp/bench.jsonl BENCH_SMOKE_BASELINE.json

Inputs are tolerant by design:

- RESULTS: a file of mixed output where every benchmark metric is one
  JSON object per line (`benchmarks/_util.emit`'s wire format:
  ``{"metric", "value", "unit", ...}``); non-JSON lines are skipped.
- BASELINE: ``BENCH_SMOKE_BASELINE.json`` — a single metric object, a JSON
  array of them, or JSON lines. Extra fields (history, notes) ignored.

Metrics are matched by exact ``metric`` name (sizes are part of the
names, so a smoke run never silently compares against a full-size
capture). For each match the verdict is direction-aware:

- units where bigger is better (rows/s, FLOP/s, bytes/s, events,
  programs, ...): regression when current < baseline * (1 - tol);
- units where smaller is better (s, ms, %, syncs, faults, retries):
  regression when current > baseline * (1 + tol).

The full table prints ALWAYS (matched and unmatched); the exit code is
1 only when a matched metric regressed beyond tolerance (default 20%,
``--tolerance 0.2``; per-metric overrides via ``--tolerance-for
'<metric>=0.5'``, repeatable). ``--require-match`` additionally fails
when NOTHING matched — the bench-regress CI lane's guard against a
renamed baseline going silently toothless is the table itself plus the
match count it prints.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

# units where a SMALLER value is the better one
_SMALLER_BETTER = (
    "s", "ms", "seconds", "%", "syncs", "faults", "retries",
    "evictions", "splits", "bytes", "shapes", "compiles", "misses",
)


def smaller_is_better(unit: str) -> bool:
    return str(unit).strip().lower() in _SMALLER_BETTER


def parse_results(text: str) -> List[Dict]:
    """Every JSON-object line carrying a numeric ``metric``/``value``
    pair; everything else (logs, warnings, asserts' prose) skipped."""
    out: List[Dict] = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if (
            isinstance(obj, dict)
            and "metric" in obj
            and isinstance(obj.get("value"), (int, float))
        ):
            out.append(obj)
    return out


def parse_baseline(text: str) -> List[Dict]:
    """A single object, an array, or JSON lines — normalized to a list
    of {"metric", "value", "unit"} entries."""
    text = text.strip()
    if not text:
        return []
    try:
        obj = json.loads(text)
        if isinstance(obj, dict):
            return [obj] if "metric" in obj else []
        if isinstance(obj, list):
            return [o for o in obj if isinstance(o, dict) and "metric" in o]
    except json.JSONDecodeError:
        pass
    return parse_results(text)


def compare(
    results: List[Dict],
    baseline: List[Dict],
    tolerance: float,
    per_metric: Optional[Dict[str, float]] = None,
) -> Tuple[List[Dict], List[Dict]]:
    """(rows, regressions). One row per CURRENT metric; baseline-only
    metrics get a trailing ``missing`` row each so a silently-dropped
    benchmark is visible in the table."""
    per_metric = per_metric or {}
    base_by_name = {b["metric"]: b for b in baseline}
    rows: List[Dict] = []
    regressions: List[Dict] = []
    seen = set()
    for r in results:
        name = r["metric"]
        seen.add(name)
        b = base_by_name.get(name)
        if b is None or not isinstance(b.get("value"), (int, float)):
            rows.append({**r, "baseline": None, "verdict": "no-baseline"})
            continue
        tol = per_metric.get(name, tolerance)
        cur, ref = float(r["value"]), float(b["value"])
        ratio = cur / ref if ref else None
        if smaller_is_better(r.get("unit", "")):
            bad = cur > ref * (1.0 + tol) and (cur - ref) > 1e-12
        else:
            bad = cur < ref * (1.0 - tol)
        row = {
            **r,
            "baseline": ref,
            "ratio": ratio,
            "tolerance": tol,
            "verdict": "REGRESSION" if bad else "ok",
        }
        rows.append(row)
        if bad:
            regressions.append(row)
    for name, b in base_by_name.items():
        if name not in seen:
            rows.append(
                {
                    "metric": name,
                    "value": None,
                    "unit": b.get("unit", ""),
                    "baseline": b.get("value"),
                    "verdict": "missing",
                }
            )
    return rows, regressions


def render(rows: List[Dict]) -> str:
    lines = [
        f"{'verdict':<12} {'ratio':>8}  {'current':>16} {'baseline':>16}"
        "  metric",
        "-" * 78,
    ]
    for r in rows:
        ratio = r.get("ratio")
        cur = r.get("value")
        ref = r.get("baseline")
        ratio_s = f"{ratio:.3f}x" if ratio is not None else "-"
        cur_s = f"{cur:g}" if cur is not None else "-"
        ref_s = f"{ref:g}" if ref is not None else "-"
        lines.append(
            f"{r['verdict']:<12} {ratio_s:>8}  {cur_s:>16} {ref_s:>16}"
            f"  {r['metric']} [{r.get('unit', '')}]"
        )
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("results", help="run output (JSON lines, mixed ok)")
    ap.add_argument("baseline", help="baseline json / array / lines")
    ap.add_argument(
        "--tolerance", type=float, default=0.20,
        help="allowed relative regression (default 0.20 = 20%%)",
    )
    ap.add_argument(
        "--tolerance-for", action="append", default=[],
        metavar="METRIC=TOL",
        help="per-metric tolerance override, repeatable",
    )
    ap.add_argument(
        "--require-match", action="store_true",
        help="fail when no metric matched the baseline at all",
    )
    args = ap.parse_args(argv)

    per_metric: Dict[str, float] = {}
    for spec in args.tolerance_for:
        name, _, tol = spec.rpartition("=")
        if not name:
            ap.error(f"--tolerance-for needs METRIC=TOL, got {spec!r}")
        per_metric[name] = float(tol)

    with open(args.results) as f:
        results = parse_results(f.read())
    with open(args.baseline) as f:
        baseline = parse_baseline(f.read())
    rows, regressions = compare(
        results, baseline, args.tolerance, per_metric
    )
    print(render(rows))
    matched = sum(1 for r in rows if r["verdict"] in ("ok", "REGRESSION"))
    print(
        f"\n{matched} matched, {len(regressions)} regression(s), "
        f"{sum(1 for r in rows if r['verdict'] == 'no-baseline')} without "
        f"baseline, {sum(1 for r in rows if r['verdict'] == 'missing')} "
        "missing from run"
    )
    if regressions:
        for r in regressions:
            print(
                f"REGRESSION: {r['metric']}: {r['value']:g} vs baseline "
                f"{r['baseline']:g} (ratio {r['ratio']:.3f}, tolerance "
                f"{r['tolerance']:.0%})",
                file=sys.stderr,
            )
        return 1
    if args.require_match and matched == 0:
        print("no metric matched the baseline", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
