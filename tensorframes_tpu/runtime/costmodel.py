"""Program cost ledger: always-on per-program cost/memory accounting.

The reference vendored `StepStats`/`NodeExecStats` protos that nothing
consumed (SURVEY §5); `api.cost_analysis` made the compiler the cost
oracle, but only on demand — answering "is this program running as fast
as the hardware allows?" meant re-lowering the graph by hand per
program. This module makes the accounting a substrate, the way
TensorFlow's runtime treats per-op cost models (PAPERS.md, "TensorFlow:
A system for large-scale machine learning"):

- **Capture at compile time.** Both executors call `capture()` when a
  program compiles a new input-shape specialization (the in-process
  `Executor._instrument` detects jit-cache growth; `NativeExecutor`
  captures at its explicit per-shape host compile). Capture lowers the
  already-traced program (`fn.lower(*args)` — tracing only, NO second
  XLA compile) and reads the compiler's modeled ``flops`` and ``bytes
  accessed``, plus exact argument/output byte counts from the concrete
  arrays. ``config.cost_ledger_memory`` opts into a real
  `memory_analysis()` (temp bytes) at the price of a second compile.
- **Count at dispatch time.** Every call of a cached program bumps its
  (kind, shape)-entry's execution count — one dict update under the
  ledger lock — so total issued flops/bytes per program are exact,
  not sampled. The verb contextvar (set by the telemetry verb span)
  attributes a per-verb high-water mark of modeled dispatch footprint.
- **Join with spans.** `tfs.diagnostics()` joins this ledger with the
  span ring's per-program execute attribution to report achieved
  FLOP/s and HBM GB/s against detected device peaks (`device_peaks`:
  datasheet table by ``device_kind``, honest ``None`` off-table).
- **Memory overview.** `memory_overview()` snapshots per-device live
  jax buffer bytes/counts and `device.memory_stats()` (bytes_in_use /
  peak_bytes_in_use where the backend reports them — TPU does, CPU
  reads None). Registered as labeled gauges, evaluated only at export
  time, and embedded in OOM forensic snapshots (`runtime.faults`).

Everything here is observability: capture and counting must NEVER
break a dispatch, so every entry point is exception-guarded and the
ledger degrades to "unknown" rather than raising.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

__all__ = [
    "DEVICE_PEAKS",
    "enabled",
    "capture",
    "note_exec",
    "program_costs",
    "program_shapes",
    "program_footprint",
    "verb_peaks",
    "device_peaks",
    "memory_overview",
    "roofline",
    "residuals",
    "reset",
]


def enabled() -> bool:
    """Cost-ledger master switch (``config.cost_ledger`` /
    ``TFS_COST_LEDGER``) — independent of the telemetry span switch."""
    from .. import config as _config

    return bool(getattr(_config.get(), "cost_ledger", True))


# ---------------------------------------------------------------------------
# device peaks (datasheet table — the ONE copy)
# ---------------------------------------------------------------------------

# Chip-level datasheet peaks by `device.device_kind`. f32 data runs the
# MXU in bf16 passes under precision=DEFAULT, so bf16 peak is the
# compute bound quoted.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    # TPU v5e: 819 GB/s HBM BW, 197 TFLOP/s bf16
    "TPU v5 lite": {"hbm_bytes_s": 819e9, "matmul_flops_s": 197e12},
    "TPU v5": {"hbm_bytes_s": 2765e9, "matmul_flops_s": 459e12},
}


def device_peaks(device=None) -> Dict[str, Optional[float]]:
    """Datasheet peaks for ``device`` (default: the first local
    device): ``{"device_kind", "hbm_bytes_s", "matmul_flops_s"}`` with
    honest ``None`` for kinds not in the table (CPU, unknown TPUs) —
    achieved-vs-peak fractions then render as "peak unknown" instead
    of inventing a denominator."""
    kind = None
    try:
        if device is None:
            import jax

            device = jax.devices()[0]
        kind = getattr(device, "device_kind", None) or getattr(
            device, "platform", None
        )
    except Exception:
        pass  # no live backend: peaks honestly read as unknown
    row = DEVICE_PEAKS.get(kind or "", {})
    return {
        "device_kind": kind,
        "hbm_bytes_s": row.get("hbm_bytes_s"),
        "matmul_flops_s": row.get("matmul_flops_s"),
    }


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

_lock = threading.Lock()
# fingerprint -> {"kinds": set, "shapes": {(kind, sig): entry},
#                 "evicted": {...}}
# entry: {"flops", "bytes_accessed", "arg_bytes", "out_bytes",
#         "temp_bytes", "execs", "capture_s", "phase"}
_programs: Dict[str, Dict] = {}
# bounds: the ledger is ALWAYS-ON in long-lived services, so — like
# the span ring, the forensics deque and _storm_warned — it must not
# grow without limit under shape churn. Past the per-program shape cap
# the oldest shape entry folds its totals into the program's "evicted"
# accumulator (totals stay exact, per-shape detail is lost); past the
# program cap the oldest program is dropped wholesale.
_MAX_SHAPES_PER_PROGRAM = 64
_MAX_PROGRAMS = 1024
# verb name -> {"bytes": high-water modeled dispatch footprint,
#               "program": fingerprint that set it, "rows": lead dim}
_verb_peaks: Dict[str, Dict] = {}


def _leaves(args) -> List:
    import jax

    return [
        l
        for l in jax.tree_util.tree_leaves(args)
        if hasattr(l, "nbytes") or hasattr(l, "shape")
    ]


def _nbytes(leaves) -> int:
    total = 0
    for l in leaves:
        nb = getattr(l, "nbytes", None)
        if nb is None:
            import numpy as np

            try:
                nb = np.asarray(l).nbytes
            except Exception:
                nb = 0
        total += int(nb)
    return total


def _sig(leaves) -> Tuple:
    return tuple(
        (tuple(getattr(l, "shape", ())), str(getattr(l, "dtype", "")))
        for l in leaves
    )


def _entry(fp: str, kind: str, sig: Tuple) -> Dict:
    """The (program, kind, shape) ledger cell — caller holds _lock."""
    prog = _programs.get(fp)
    if prog is None:
        while len(_programs) >= _MAX_PROGRAMS:
            _programs.pop(next(iter(_programs)))
        prog = {
            "kinds": set(), "shapes": {},
            "evicted": {"execs": 0, "flops": 0.0, "bytes": 0.0},
        }
        _programs[fp] = prog
    prog["kinds"].add(kind)
    ent = prog["shapes"].get((kind, sig))
    if ent is None:
        while len(prog["shapes"]) >= _MAX_SHAPES_PER_PROGRAM:
            old = prog["shapes"].pop(next(iter(prog["shapes"])))
            ev = prog["evicted"]
            ev["execs"] += old["execs"]
            if old["flops"] is not None:
                ev["flops"] += old["flops"] * max(1, old["execs"])
            if old["bytes_accessed"] is not None:
                ev["bytes"] += old["bytes_accessed"] * max(1, old["execs"])
        ent = {
            "flops": None, "bytes_accessed": None,
            "arg_bytes": None, "out_bytes": None, "temp_bytes": None,
            "execs": 0, "capture_s": 0.0, "phase": None, "rows": None,
        }
        prog["shapes"][(kind, sig)] = ent
    return ent


def _lead_rows(leaves) -> Optional[int]:
    for l in leaves:
        shp = getattr(l, "shape", ())
        if shp:
            return int(shp[0])
    return None


def capture(key: Tuple, fn, args, lowered=None, phase: str = "xla") -> None:
    """Record the compiler's modeled cost for one freshly compiled
    (program, shape): called by `Executor._instrument` when a dispatch
    grows the jit cache (``lowered`` is derived via ``fn.lower(*args)``
    — tracing + HLO cost analysis, no second XLA compile) and by
    `NativeExecutor._native_run` with the `Lowered` it already holds.
    ``config.cost_ledger_memory`` additionally compiles the module to
    read temp bytes. Never raises — a capture failure leaves the entry
    at honest None and the dispatch result untouched."""
    if not enabled():
        return
    import time

    fp, kind = str(key[1]), str(key[0])
    t0 = time.perf_counter()
    flops = bytes_accessed = temp = None
    try:
        if lowered is None:
            lowered = fn.lower(*args)
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax returns [dict]
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            flops = float(ca.get("flops", 0.0)) or None
            bytes_accessed = float(ca.get("bytes accessed", 0.0)) or None
        from .. import config as _config

        if _config.get().cost_ledger_memory:
            mem = lowered.compile().memory_analysis()
            temp = float(getattr(mem, "temp_size_in_bytes", 0) or 0)
    except Exception:
        pass  # the ledger degrades to unknown, never breaks a dispatch
    try:
        leaves = _leaves(args)
        sig = _sig(leaves)
        arg_bytes = _nbytes(leaves)
        rows = _lead_rows(leaves)
        dt = time.perf_counter() - t0
        with _lock:
            ent = _entry(fp, kind, sig)
            ent["flops"] = flops
            ent["bytes_accessed"] = bytes_accessed
            if temp is not None:
                ent["temp_bytes"] = temp
            ent["arg_bytes"] = arg_bytes
            ent["rows"] = rows
            ent["capture_s"] += dt
            ent["phase"] = ent["phase"] or phase
    except Exception:
        pass  # the ledger degrades to unknown, never breaks a compile


def note_exec(key: Tuple, args, out, verb: Optional[str] = None) -> None:
    """Count one execution of a cached program against its (kind,
    shape) ledger cell and update the per-verb footprint high-water
    mark. The per-dispatch cost is a handful of metadata reads and one
    locked dict update; never raises."""
    if not enabled():
        return
    try:
        fp, kind = str(key[1]), str(key[0])
        in_leaves = _leaves(args)
        sig = _sig(in_leaves)
        arg_bytes = _nbytes(in_leaves)
        out_bytes = _nbytes(_leaves(out))
        rows = _lead_rows(in_leaves)
        if verb is None:
            from ..utils import telemetry as _tele

            verb = _tele.current_verb()
        with _lock:
            ent = _entry(fp, kind, sig)
            ent["execs"] += 1
            if ent["arg_bytes"] is None:
                ent["arg_bytes"] = arg_bytes
            if ent["out_bytes"] is None:
                ent["out_bytes"] = out_bytes
            if ent["rows"] is None:
                ent["rows"] = rows
            footprint = arg_bytes + out_bytes + (ent["temp_bytes"] or 0)
            if verb:
                peak = _verb_peaks.get(verb)
                if peak is None or footprint > peak["bytes"]:
                    _verb_peaks[verb] = {
                        "bytes": footprint, "program": fp, "rows": rows,
                    }
    except Exception:
        pass  # exec accounting must never break the dispatch it counts


def program_costs() -> Dict[str, Dict]:
    """Ledger snapshot aggregated per program fingerprint:
    ``{fp: {kinds, shapes, execs, total_flops, total_bytes_accessed,
    footprint_bytes, flops_per_exec, bytes_per_exec, temp_known,
    capture_s}}``. Totals are Σ over shape entries of (per-shape cost x
    per-shape exec count) — exact for what the compiler modeled;
    ``None`` totals mean no shape of the program captured that
    quantity (cost analysis unavailable)."""
    with _lock:
        progs = {
            fp: {
                "kinds": set(p["kinds"]),
                "shapes": dict(p["shapes"]),
                "evicted": dict(p["evicted"]),
            }
            for fp, p in _programs.items()
        }
    out: Dict[str, Dict] = {}
    for fp, p in progs.items():
        ev = p["evicted"]
        total_flops = float(ev["flops"])
        total_ba = float(ev["bytes"])
        flops_known = ev["flops"] > 0
        ba_known = ev["bytes"] > 0
        execs = ev["execs"]
        footprint = 0
        temp_known = False
        capture_s = 0.0
        per_exec_flops = per_exec_ba = None
        for (kind, sig), ent in p["shapes"].items():
            execs += ent["execs"]
            capture_s += ent["capture_s"]
            if ent["flops"] is not None:
                flops_known = True
                total_flops += ent["flops"] * max(1, ent["execs"])
                # per-exec columns report the LARGEST captured shape —
                # the one an OOM forensic snapshot and a roofline eye
                # care about — not an arbitrary iteration-order pick
                if per_exec_flops is None or ent["flops"] > per_exec_flops:
                    per_exec_flops = ent["flops"]
            if ent["bytes_accessed"] is not None:
                ba_known = True
                total_ba += ent["bytes_accessed"] * max(1, ent["execs"])
                if per_exec_ba is None or ent["bytes_accessed"] > per_exec_ba:
                    per_exec_ba = ent["bytes_accessed"]
            if ent["temp_bytes"] is not None:
                temp_known = True
            fp_bytes = (
                (ent["arg_bytes"] or 0)
                + (ent["out_bytes"] or 0)
                + (ent["temp_bytes"] or 0)
            )
            footprint = max(footprint, fp_bytes)
        out[fp] = {
            "kinds": sorted(p["kinds"]),
            "shapes": len(p["shapes"]),
            "execs": execs,
            "total_flops": total_flops if flops_known else None,
            "total_bytes_accessed": total_ba if ba_known else None,
            "flops_per_exec": per_exec_flops,
            "bytes_per_exec": per_exec_ba,
            "footprint_bytes": footprint or None,
            "temp_known": temp_known,
            "capture_s": capture_s,
        }
    return out


def modeled_recompute_s(fp: str) -> Optional[float]:
    """Predicted seconds to recompute ONE execution of program ``fp``:
    the ledger's per-exec modeled cost (largest captured shape)
    converted through the fitted effective throughput of
    `residuals()` — the admission price the materialization cache
    compares against its measured store+load cost. ``None`` when the
    ledger has no costed shape for the program or no residual fit
    exists yet (no dispatch spans to fit against)."""
    if not enabled():
        return None
    costs = program_costs().get(fp)
    if costs is None:
        return None
    try:
        fit = residuals()["fit"]
    except Exception:
        return None
    pred = None
    if costs["bytes_per_exec"] is not None and fit.get("bytes_per_s"):
        pred = costs["bytes_per_exec"] / fit["bytes_per_s"]
    elif costs["flops_per_exec"] is not None and fit.get("flops_per_s"):
        pred = costs["flops_per_exec"] / fit["flops_per_s"]
    return pred


def program_shapes() -> Dict[str, List[Dict]]:
    """Per-(program, kind, shape) ledger detail: one row per captured
    shape entry with its lead row count (the BUCKET rows of a padded
    dispatch — what joins against dispatch-span ``bucket``/``rows``
    labels), exec count and modeled costs. The workload profiler and
    the residual join read this; `program_costs` stays the aggregated
    view."""
    with _lock:
        return {
            fp: [
                {
                    "kind": kind,
                    "rows": ent["rows"],
                    "execs": ent["execs"],
                    "flops": ent["flops"],
                    "bytes_accessed": ent["bytes_accessed"],
                    "arg_bytes": ent["arg_bytes"],
                    "out_bytes": ent["out_bytes"],
                    "temp_bytes": ent["temp_bytes"],
                    "phase": ent["phase"],
                }
                for (kind, _sig_), ent in p["shapes"].items()
            ]
            for fp, p in _programs.items()
        }


def program_footprint(fp: str) -> Optional[Dict]:
    """The modeled footprint of one program fingerprint (for OOM
    forensics): max over captured shapes of argument + output (+ temp
    when deep capture ran) bytes, plus per-exec flops/bytes. None when
    the program never reached the ledger."""
    costs = program_costs().get(str(fp))
    if costs is None:
        return None
    return {
        "footprint_bytes": costs["footprint_bytes"],
        "flops_per_exec": costs["flops_per_exec"],
        "bytes_per_exec": costs["bytes_per_exec"],
        "temp_known": costs["temp_known"],
        "shapes": costs["shapes"],
    }


def verb_peaks() -> Dict[str, Dict]:
    """Per-verb high-water marks of modeled dispatch footprint
    (argument + output + known-temp bytes of the largest single
    dispatch that verb issued). Attribution rides the telemetry verb
    contextvar, so dispatches outside any verb span pool under no key."""
    with _lock:
        return {k: dict(v) for k, v in _verb_peaks.items()}


# ---------------------------------------------------------------------------
# device memory introspection
# ---------------------------------------------------------------------------


def memory_overview() -> List[Dict]:
    """One row per local device: live jax buffer bytes/count committed
    to it, and the backend's ``memory_stats()`` (``bytes_in_use`` /
    ``peak_bytes_in_use``) where reported — None elsewhere (the CPU
    backend reports nothing; honesty over invention). Sharded arrays
    attribute nbytes / ndevices to each holder."""
    try:
        import jax

        from .scheduler import device_label

        devices = list(jax.local_devices())
    except Exception:
        return []
    rows = {
        device_label(d): {
            "device": device_label(d),
            "device_kind": getattr(d, "device_kind", None),
            "live_buffer_bytes": 0,
            "live_buffers": 0,
            "bytes_in_use": None,
            "peak_bytes_in_use": None,
        }
        for d in devices
    }
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms:
            lab = device_label(d)
            rows[lab]["bytes_in_use"] = ms.get("bytes_in_use")
            rows[lab]["peak_bytes_in_use"] = ms.get("peak_bytes_in_use")
    try:
        import jax

        from .scheduler import device_label

        for a in jax.live_arrays():
            try:
                ds = list(a.devices())
                share = int(a.nbytes) // max(1, len(ds))
                for d in ds:
                    lab = device_label(d)
                    if lab in rows:
                        rows[lab]["live_buffer_bytes"] += share
                        rows[lab]["live_buffers"] += 1
            except Exception:
                continue
    except Exception:
        pass  # backend gone mid-probe: report the rows gathered so far
    return [rows[k] for k in sorted(rows)]


def _register_gauges() -> None:
    """Labeled device-memory gauges, evaluated ONLY at export time (a
    scrape walks live_arrays once; dispatches never pay for this)."""
    from ..utils import telemetry as _tele

    def _live() -> Dict[str, float]:
        return {
            r["device"]: float(r["live_buffer_bytes"])
            for r in memory_overview()
        }

    def _in_use() -> Dict[str, float]:
        return {
            r["device"]: float(r["bytes_in_use"])
            for r in memory_overview()
            if r["bytes_in_use"] is not None
        }

    def _peak() -> Dict[str, float]:
        return {
            r["device"]: float(r["peak_bytes_in_use"])
            for r in memory_overview()
            if r["peak_bytes_in_use"] is not None
        }

    _tele.gauge_register_multi("live_buffer_bytes", "device", _live)
    _tele.gauge_register_multi("device_bytes_in_use", "device", _in_use)
    _tele.gauge_register_multi("device_peak_bytes", "device", _peak)


# ---------------------------------------------------------------------------
# the roofline join (ledger x span attribution)
# ---------------------------------------------------------------------------


def roofline(by_program: Dict[str, Dict]) -> List[Dict]:
    """Join the ledger with the span ring's per-program execute
    attribution (`telemetry.span_aggregates()["by_program"]`): one row
    per fingerprint with modeled totals and achieved FLOP/s + HBM GB/s
    over the attributed execute seconds, as fractions of the detected
    device peaks (None when the peak — or the cost — is unknown).
    Execute seconds are async ISSUE windows (the documented span
    caveat), so fractions are a floor estimate on sync-bound chains."""
    peaks = device_peaks()
    costs = program_costs()
    rows: List[Dict] = []
    fps = sorted(set(costs) | set(by_program))
    for fp in fps:
        c = costs.get(fp)
        p = by_program.get(fp, {})
        exec_s = float(p.get("execute_s", 0.0))
        row = {
            "program": fp,
            "execs": c["execs"] if c else 0,
            "shapes": c["shapes"] if c else 0,
            "flops_per_exec": c["flops_per_exec"] if c else None,
            "bytes_per_exec": c["bytes_per_exec"] if c else None,
            "total_flops": c["total_flops"] if c else None,
            "total_bytes_accessed": (
                c["total_bytes_accessed"] if c else None
            ),
            "footprint_bytes": c["footprint_bytes"] if c else None,
            "temp_known": c["temp_known"] if c else False,
            "execute_s": exec_s,
            "dispatches": int(p.get("dispatches", 0)),
            "achieved_flops_s": None,
            "achieved_hbm_bytes_s": None,
            "flops_frac_of_peak": None,
            "hbm_frac_of_peak": None,
        }
        # achieved rates pair the SPAN WINDOW's dispatch count with the
        # span window's execute seconds (the ledger's exec totals are
        # cumulative since reset and outlive the bounded span ring — a
        # wrapped ring would otherwise inflate achieved past peak);
        # per-dispatch cost is the ledger's cumulative average
        disp = int(p.get("dispatches", 0))
        if c and exec_s > 0 and c["execs"] and disp:
            if c["total_flops"] is not None:
                avg = c["total_flops"] / c["execs"]
                row["achieved_flops_s"] = avg * disp / exec_s
                if peaks["matmul_flops_s"]:
                    row["flops_frac_of_peak"] = (
                        row["achieved_flops_s"] / peaks["matmul_flops_s"]
                    )
            if c["total_bytes_accessed"] is not None:
                avg = c["total_bytes_accessed"] / c["execs"]
                row["achieved_hbm_bytes_s"] = avg * disp / exec_s
                if peaks["hbm_bytes_s"]:
                    row["hbm_frac_of_peak"] = (
                        row["achieved_hbm_bytes_s"] / peaks["hbm_bytes_s"]
                    )
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# cost-model accuracy: modeled vs span-achieved residuals
# ---------------------------------------------------------------------------


def residuals(span_list=None) -> Dict:
    """How wrong is the cost model, per (program x dispatched shape)?

    Joins the span ring's per-dispatch achieved seconds (grouped by
    (program fingerprint, dispatched lead rows — the bucket rung of a
    padded dispatch)) with the ledger's modeled cost for that same
    shape, then scores each group against a PREDICTED time. Predictions
    use a per-process *effective throughput* fitted over every joined
    group (Σ modeled bytes / Σ achieved seconds, and the flops
    analogue), so the residual is RELATIVE: ``residual_ratio`` = 1
    means the program's achieved time sits exactly where the model's
    cost ranks it among its peers; a ratio far from 1 means the model
    misprices this program relative to the rest of the workload — the
    signal a cost-based planner needs before trusting plan prices.
    With a single joined group the fit is exact by construction
    (ratio 1.0); accuracy needs workload diversity, honestly so.

    When datasheet peaks are known (TPU), each group also carries the
    ABSOLUTE roofline time (``modeled_peak_s``) and its ratio; on
    peak-less backends those read None rather than invented.

    Dispatch spans measure async issue windows (the documented span
    caveat), so achieved seconds are a floor on sync-bound chains.
    Returns ``{"warn_ratio", "fit", "groups", "programs"}``; programs
    whose ratio exceeds ``config.cost_residual_warn_ratio`` (either
    direction) are ``flagged``."""
    from .. import config as _config
    from ..utils import telemetry as _tele

    ss = _tele.spans() if span_list is None else span_list
    # achieved: (program, dispatched lead rows) -> seconds / count
    groups: Dict[Tuple[str, Optional[int]], Dict] = {}
    for s in ss:
        if s.kind != "dispatch":
            continue
        prog = s.attrs.get("program")
        if not prog:
            continue
        rows = s.attrs.get("rows")
        bucket = s.attrs.get("bucket")
        lead = bucket if bucket is not None else rows
        lead = int(lead) if lead is not None else None
        g = groups.setdefault(
            (str(prog), lead),
            {"seconds": 0.0, "dispatches": 0, "rows": 0.0},
        )
        g["seconds"] += s.seconds
        g["dispatches"] += 1
        g["rows"] += float(rows or 0)
        g.setdefault("op_class", _op_class(s.name))
    # first-call XLA shape specializations happen INSIDE the dispatch
    # window (jit compiles on call), so a program's compile spans are
    # subtracted from its achieved dispatch seconds — the residual
    # scores the model against steady-state execution, not against a
    # one-off compile the model never claimed to price. The subtraction
    # distributes proportionally across the program's shape groups and
    # floors at 1% of the raw window (a wholly-compile-bound window
    # still yields a finite, pessimistic-but-not-zero achieved time).
    compile_s: Dict[str, float] = {}
    for s in ss:
        if s.kind == "compile":
            prog = s.attrs.get("program")
            if prog:
                fp = str(prog)
                compile_s[fp] = compile_s.get(fp, 0.0) + s.seconds
    prog_disp_s: Dict[str, float] = {}
    for (fp, _lead), g in groups.items():
        prog_disp_s[fp] = prog_disp_s.get(fp, 0.0) + g["seconds"]
    for (fp, _lead), g in groups.items():
        cs = compile_s.get(fp, 0.0)
        tot = prog_disp_s.get(fp, 0.0)
        if cs > 0 and tot > 0:
            g["compile_s_excluded"] = cs * (g["seconds"] / tot)
            g["seconds"] = max(
                0.01 * g["seconds"], g["seconds"] - g["compile_s_excluded"]
            )
    shapes = program_shapes()

    def _modeled(fp: str, lead: Optional[int]) -> Tuple:
        ents = shapes.get(fp) or []
        match = [e for e in ents if e["rows"] == lead]
        if not match and len(ents) == 1:
            match = ents  # one captured shape: the only candidate
        if not match:
            return None, None
        e = max(match, key=lambda e: e["execs"])
        by = e["bytes_accessed"]
        if by is None and e["arg_bytes"] is not None:
            by = e["arg_bytes"] + (e["out_bytes"] or 0)
        return e["flops"], by

    joined = []
    for (fp, lead), g in groups.items():
        flops, by = _modeled(fp, lead)
        joined.append(
            {
                "program": fp,
                "rows": lead,
                "op_class": g.get("op_class", "map"),
                "dispatches": g["dispatches"],
                "achieved_s": g["seconds"],
                "compile_s_excluded": g.get("compile_s_excluded", 0.0),
                "modeled_flops": flops,
                "modeled_bytes": by,
            }
        )
    fit_b_num = fit_b_den = fit_f_num = fit_f_den = 0.0
    # per-op-class rollup (map / reduce / relational): the planner's
    # calibrated throughput for program fingerprints it never dispatched
    cls_fit: Dict[str, Dict[str, float]] = {}
    for r in joined:
        if r["achieved_s"] <= 0:
            continue
        c = cls_fit.setdefault(
            r["op_class"],
            {"b_num": 0.0, "b_den": 0.0, "f_num": 0.0, "f_den": 0.0,
             "groups": 0},
        )
        c["groups"] += 1
        if r["modeled_bytes"] is not None:
            fit_b_num += r["modeled_bytes"] * r["dispatches"]
            fit_b_den += r["achieved_s"]
            c["b_num"] += r["modeled_bytes"] * r["dispatches"]
            c["b_den"] += r["achieved_s"]
        if r["modeled_flops"] is not None:
            fit_f_num += r["modeled_flops"] * r["dispatches"]
            fit_f_den += r["achieved_s"]
            c["f_num"] += r["modeled_flops"] * r["dispatches"]
            c["f_den"] += r["achieved_s"]
    eff_bytes = fit_b_num / fit_b_den if fit_b_den > 0 else None
    eff_flops = fit_f_num / fit_f_den if fit_f_den > 0 else None
    by_class = {
        cls: {
            "bytes_per_s": c["b_num"] / c["b_den"] if c["b_den"] > 0 else None,
            "flops_per_s": c["f_num"] / c["f_den"] if c["f_den"] > 0 else None,
            "groups": c["groups"],
        }
        for cls, c in cls_fit.items()
    }
    peaks = device_peaks()
    warn = float(
        getattr(_config.get(), "cost_residual_warn_ratio", 0.0) or 0.0
    )
    per_prog: Dict[str, Dict] = {}
    for r in joined:
        pred = None
        # prefer the bytes model (dataframe verbs are bandwidth-shaped);
        # flops is the fallback when bytes never captured
        if r["modeled_bytes"] is not None and eff_bytes:
            pred = r["modeled_bytes"] / eff_bytes
        elif r["modeled_flops"] is not None and eff_flops:
            pred = r["modeled_flops"] / eff_flops
        r["predicted_s_per_exec"] = pred
        ach = (
            r["achieved_s"] / r["dispatches"] if r["dispatches"] else None
        )
        r["achieved_s_per_exec"] = ach
        r["residual_ratio"] = (
            ach / pred if (pred and ach is not None and pred > 0) else None
        )
        peak_s = None
        if r["modeled_flops"] is not None and peaks["matmul_flops_s"]:
            peak_s = r["modeled_flops"] / peaks["matmul_flops_s"]
        if r["modeled_bytes"] is not None and peaks["hbm_bytes_s"]:
            hb = r["modeled_bytes"] / peaks["hbm_bytes_s"]
            peak_s = hb if peak_s is None else max(peak_s, hb)
        r["modeled_peak_s"] = peak_s
        r["peak_ratio"] = (
            ach / peak_s if (peak_s and ach is not None) else None
        )
        p = per_prog.setdefault(
            r["program"],
            {"achieved_s": 0.0, "predicted_s": 0.0, "dispatches": 0,
             "worst_group_ratio": None},
        )
        p["dispatches"] += r["dispatches"]
        if pred is not None:
            p["achieved_s"] += r["achieved_s"]
            p["predicted_s"] += pred * r["dispatches"]
            rr = r["residual_ratio"]
            if rr is not None and (
                p["worst_group_ratio"] is None
                or abs(_log2(rr)) > abs(_log2(p["worst_group_ratio"]))
            ):
                p["worst_group_ratio"] = rr
    for fp, p in per_prog.items():
        ratio = (
            p["achieved_s"] / p["predicted_s"]
            if p["predicted_s"] > 0
            else None
        )
        p["residual_ratio"] = ratio
        p["flagged"] = bool(
            warn > 0
            and ratio is not None
            and (ratio > warn or ratio < 1.0 / warn)
        )
    return {
        "warn_ratio": warn,
        "fit": {
            "bytes_per_s": eff_bytes,
            "flops_per_s": eff_flops,
            "groups": len(joined),
        },
        "by_class": by_class,
        "groups": sorted(
            joined, key=lambda r: (r["program"], r["rows"] or 0)
        ),
        "programs": per_prog,
    }


def _op_class(span_name: str) -> str:
    """map / reduce / relational bucket for a dispatch span — coarse
    on purpose: the planner wants a calibrated figure for op SHAPES it
    has never dispatched, and three bandwidth classes is what the
    ledger can actually distinguish."""
    n = span_name or ""
    if n.startswith("plan."):
        return "relational"
    if "reduce" in n or "aggregate" in n:
        return "reduce"
    return "map"


def planner_throughput(op_class: str) -> Optional[float]:
    """Residuals-corrected effective bytes/second for one op class
    (``map`` / ``reduce`` / ``relational``): the per-class fit when
    that class has dispatched, else the process-wide fit, else None
    (the optimizer then uses its cold-start default). This is the
    costing rule's measurement side — rewrites are priced against what
    this process actually achieved, not a heuristic table."""
    try:
        res = residuals()
    except Exception:
        return None
    ent = (res.get("by_class") or {}).get(op_class)
    if ent and ent.get("bytes_per_s"):
        return float(ent["bytes_per_s"])
    fit = res.get("fit") or {}
    v = fit.get("bytes_per_s")
    return float(v) if v else None


def _log2(x: float) -> float:
    import math

    return math.log2(x) if x > 0 else 0.0


def _register_residual_gauge() -> None:
    """``costmodel_residual{program=}``: the per-program residual ratio
    as a registered gauge family — evaluated only at export (a scrape
    walks the span ring once, same cost class as /diagnostics)."""
    from ..utils import telemetry as _tele

    def _residual() -> Dict[str, float]:
        out: Dict[str, float] = {}
        for fp, p in residuals()["programs"].items():
            if p.get("residual_ratio") is not None:
                out[fp] = float(p["residual_ratio"])
        return out

    _tele.gauge_register_multi("costmodel_residual", "program", _residual)


def reset() -> None:
    """Clear the ledger and verb peaks (test isolation — the conftest
    autouse fixture calls this beside `telemetry.reset()`)."""
    with _lock:
        _programs.clear()
        _verb_peaks.clear()


_register_gauges()
_register_residual_gauge()
