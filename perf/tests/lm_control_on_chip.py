"""Readings for the limits of `lfm2_score_4k`'s `correct`, on the chip at
the cell's own size; not part of a benchmark run and not a pytest file.

    python3 perf/tests/lm_control_on_chip.py --seeds 1,2,... \
        --control-seeds 101,... --fault-seeds 201

One process, a JSON line a seed (also appended to
`chiprun_out/lm_control.jsonl`). Everything goes through the runner's own
`check` and the harness's `decide`, at the limits the configuration's file
holds. For each seed of `--seeds`: the cell's runner is built at the
cell's traffic, one verb call scores the frame, and its outputs are
judged (`sound`: the lower reading of each limit is the largest of
these); `float32` is the same outputs against the plain float32
reference along the same routing (how far the stated precision is from
exact arithmetic), not judged. For each seed of `--control-seeds`
besides: the reference one step down in precision from what the
configuration states stands in the program's place (`lm_plants.CONTROLS`;
the upper reading is the smallest of `sums_128`). For each seed of
`--fault-seeds`: each of `lm_plants.FAULTS` planted in a fresh runner, a
short window measured as `perf/run.py` measures it, and judged.
`--rehearse` runs the rehearsal sizes on any backend.
"""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lm_plants  # noqa: E402
from perf.lib import harness  # noqa: E402

CELL = "lfm2_score_4k"


def judged(compared, wrong, raised=0):
    out = {k: v["value"] for k, v in compared.items()}
    out["wrong"] = wrong
    out["correct"] = bool(harness.decide(compared, raised))
    return out


def more(runner, got):
    """Beside `compare`'s 99th percentile: the median, the mean and the
    largest |log-probability error| along `got`'s routing."""
    want = runner.reference_rows(
        runner.check_rows, routing=got[2], operands=runner.model["dtype"])
    err = np.abs(np.asarray(got[0], np.float64) - want[0])[:, :-1]
    return {"p50": float(np.median(err)), "mean": float(err.mean()),
            "max": float(err.max())}


def one_seed(cell, config, traffic, seed, rehearse, controls, faults):
    import jax

    def build():
        env = harness.make_env(ROOT, cell, config, traffic, seed, rehearse)
        return env, harness.make_runner(env)

    env, runner = build()
    out = runner.issue()
    jax.block_until_ready(out)
    line = {"seed": seed, "rows": runner.check_rows,
            "sound": judged(*lm_plants.judge_in_the_programs_place(runner, out))}
    got = [np.asarray(a)[runner.check_rows] for a in out]
    exact = runner.reference_rows(runner.check_rows, routing=got[2])
    line["float32"] = env.reference.compare(
        got, exact, runner.model["num_experts_per_tok"])
    line["sound"].update(more(runner, got))
    if controls:
        for name, how in lm_plants.CONTROLS[runner.model["dtype"]].items():
            low = runner.reference_rows(runner.check_rows, **how)
            line[name] = judged(*lm_plants.judge_in_the_programs_place(runner, low))
            line[name].update(more(runner, low))
    del runner, env, out
    gc.collect()
    for name in faults:
        env, runner = build()
        lm_plants.FAULTS[name](env, runner)
        got = harness.measure(env, runner, 0.2 if rehearse else 2.0)
        line[name] = judged(*runner.check(), raised=got.summary["raised"])
        del runner, env, got
        gc.collect()
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    _, cell, config, traffic = harness.load_cell(ROOT, CELL)
    if args.rehearse:
        traffic = {**traffic, **traffic["rehearse"]}
    else:
        import tensorframes_tpu as tfs

        tfs.config.enable_compilation_cache()
    ints = lambda text: [int(s) for s in text.split(",") if s]
    controls, faults = set(ints(args.control_seeds)), set(ints(args.fault_seeds))
    seeds = ints(args.seeds)
    seeds += sorted((controls | faults) - set(seeds))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for seed in seeds:
        line = one_seed(cell, config, traffic, seed, args.rehearse, seed in controls,
                        sorted(lm_plants.FAULTS) if seed in faults else [])
        print(json.dumps(line), flush=True)
        with open(os.path.join(ROOT, "chiprun_out", "lm_control.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
