"""The route `map_chain_200blocks_4chips` takes, as the package's own
counters show it: one window of the cell through `harness.measure`, then
one JSON line with the counters over the window, the calls, and what the
three readers of the scheduler's books (`d2d_host_ms_per_call`,
`d2d_bytes_per_call`, `dispatch_balance_pct`) make of them. Not a pytest
file: `test_four_chips_cell.py` runs it on four virtual CPU devices at
the rehearsal sizes, and it runs on the chips as

    chiprun --chips 4 -- python3 perf/tests/four_chips_route.py --seed <n> --seconds 5

`--faults` (rehearsal only) adds the verdict of `correct` for a sound run
and for each of `test_faults.py`'s three planted faults on this cell."""

import argparse
import importlib
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from perf.lib import harness  # noqa: E402

CELL = "map_chain_200blocks_4chips"
READERS = ("d2d_host_ms_per_call", "d2d_bytes_per_call", "dispatch_balance_pct")


def read_all(counters, summary, rows_per_call):
    ctx = types.SimpleNamespace(
        counters=counters, window=summary, rows_per_call=rows_per_call)
    return {name: importlib.import_module("perf.metrics." + name).read(ctx)
            for name in READERS}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)

    import jax

    devs = jax.local_devices()
    if len(devs) < 4 or (not args.rehearse and devs[0].platform != "tpu"):
        print(f"four_chips_route.py: needs four devices (TPU chips unless "
              f"--rehearse); jax found {len(devs)} x {devs[0].platform}",
              file=sys.stderr)
        return 1
    _, cell, config, traffic = harness.load_cell(ROOT, CELL)
    if args.rehearse:
        traffic = {**traffic, **traffic["rehearse"]}
    env = harness.make_env(ROOT, cell, config, traffic, args.seed, args.rehearse)
    runner = harness.make_runner(env)
    got = harness.measure(env, runner, args.seconds)
    compared, wrong = runner.check()
    from tensorframes_tpu import shape_policy

    rows, blocks = int(traffic["rows"]), int(traffic["blocks"])
    out = {
        "devices": [f"{d.platform}:{d.id}" for d in devs],
        "rows": rows, "blocks": blocks,
        "bucket": int(shape_policy.bucket_for(rows // blocks)),
        "calls": got.summary["attempted"], "raised": got.summary["raised"],
        "correct": harness.decide(compared, got.summary["raised"]) and not wrong,
        "counters": {k: v for k, v in sorted(got.counters.items())
                     if k.startswith(("scheduler.", "shape_bucketing."))},
        "readers": read_all(got.counters, got.summary, runner.rows_per_call),
        "readers_on_no_counters": read_all({}, got.summary, runner.rows_per_call),
    }
    if args.faults:
        import test_faults

        out["faults"] = {
            name: test_faults.drive(
                CELL, seed=77,
                break_path=test_faults.patched("map_blocks", fault))[0]
            for name, fault in sorted(test_faults.FAULTS.items())
        }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
