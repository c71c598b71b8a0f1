"""The sets of runs that the bounds in `BENCHMARK.json` were set from;
not part of a benchmark run and not a pytest file.

    python3 perf/tests/sets_on_chip.py --workload <cell> --seconds <run_seconds> \
        --seeds 11,2147483659,... --sets 2 --trace-seeds 51,2147480052,...

Runs `perf/run.py` once per seed and set, each a process of its own as
the driver starts them (this parent never touches jax), then the traced
runs; appends every result line to `chiprun_out/sets_<cell>.jsonl` and
prints each end-to-end metric's median and spread per set. A spread is
the distance between the first and third quartile as
`statistics.quantiles(values, n=4)` gives them, over the median.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def run_once(cell, seed, seconds, trace):
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True,
    )
    lines = p.stdout.strip().splitlines()
    if p.returncode != 0 or not lines:
        print(f"seed {seed}: rc {p.returncode}\n{p.stderr[-3000:]}", file=sys.stderr)
        return None
    return json.loads(lines[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--tag", default="", help="kept on every line of the log")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"sets_{args.workload}.jsonl"), "a")
    ok = True

    def record(kind, seed, trace):
        nonlocal ok
        line = run_once(args.workload, seed, args.seconds, trace)
        log.write(json.dumps({"set": kind, "tag": args.tag, "seconds": args.seconds,
                              "line": line}) + "\n")
        log.flush()
        ok = ok and line is not None and line["correct"]
        return line

    for k in range(args.sets):
        got = [ln for ln in (record(f"set{k + 1}", s, 0) for s in seeds) if ln]
        if len(got) < 2:
            continue
        for name in got[0]["metrics"]:
            v = [ln["metrics"][name]["value"] for ln in got]
            print(f"{args.workload} {args.tag} set{k + 1} {name}: median "
                  f"{statistics.median(v)!r} spread {100 * spread(v):.3f}% "
                  f"values {v}", flush=True)
        print(f"{args.workload} {args.tag} set{k + 1} attempted "
              f"{[ln['attempted'] for ln in got]} correct "
              f"{[ln['correct'] for ln in got]}", flush=True)
    for s in [int(x) for x in args.trace_seeds.split(",") if x]:
        ln = record("traced", s, 1)
        if ln:
            print(f"{args.workload} {args.tag} traced seed {s} correct {ln['correct']} "
                  f"{ {k: v['value'] for k, v in ln['metrics'].items()} } busy_s "
                  f"{ln['device']['busy_s']} window_s {ln['device']['window_s']}",
                  flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
