"""Every device operation of a cell's traced slice, not the ten longest
`perf/run.py` hands the metric readers; not part of a benchmark run and
not a pytest file.

    python3 perf/tests/full_trace_on_chip.py --workload <cell> --seed 1 [--top 60]

Builds the cell's runner, warms it as `perf/run.py` does, measures a short
untraced window and the cell's traced slice (`harness.measure`), reduces
the trace with `trace.reduce_events(..., top=<top>)` and prints one JSON
line: the slice's seconds, calls, the program's device seconds and the
`top` longest operations by label (also written to
`chiprun_out/full_trace_<cell>.json`). `--rehearse` runs the rehearsal
sizes on any backend (a CPU trace has no device plane: it prints that)."""

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.lib import harness, trace as trace_lib  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--top", type=int, default=60)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    _, cell, config, traffic = harness.load_cell(ROOT, args.workload)
    if args.rehearse:
        traffic = {**traffic, **traffic["rehearse"]}
    env = harness.make_env(ROOT, cell, config, traffic, args.seed, args.rehearse)
    if not args.rehearse:
        env.tfs.config.enable_compilation_cache()
    runner = harness.make_runner(env)
    trace_dir = os.path.join(ROOT, "perf", ".trace", cell["name"] + ".full")
    got = harness.measure(env, runner, 0.3 if args.rehearse else args.seconds, trace_dir,
                          0.3 if args.rehearse else traffic.get("trace_seconds", 3.0))
    events = trace_lib.load_events(trace_lib.find_xplane(trace_dir))
    reduced = trace_lib.reduce_events(events, config["program_modules"], top=args.top)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if reduced is None:
        print(json.dumps({"workload": cell["name"], "device_plane": None}))
        return
    line = {
        "workload": cell["name"], "seed": args.seed,
        "traced_calls": len([c for c in got.traced_calls if c.error is None]),
        "rows_per_call": runner.rows_per_call,
        "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
        "program_seconds": reduced["program_seconds"],
        "device_ops": reduced["device_ops"],
    }
    print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"full_trace_{cell['name']}.json"), "w") as f:
        json.dump(line, f)


if __name__ == "__main__":
    main()
