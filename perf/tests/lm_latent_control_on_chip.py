"""Readings for the limits of `joyai_score_32k`'s `correct`, on the chip at
the cell's own size; not part of a benchmark run and not a pytest file.

    python3 perf/tests/lm_latent_control_on_chip.py --seeds 1,2,... \
        --control-seeds 101,... --fault-seeds 201 [--exact-seeds 1]

`lm_control_on_chip.py`'s readings for the latent-attention cell, one
reference pass a reading (a pass over two 32,768-token rows evaluates
every one of 256 experts densely: about half a minute). One process, a
JSON line a seed (also appended to `chiprun_out/lm_latent_control.jsonl`).
Everything goes through the runner's own `check` and the harness's
`decide`, at the limits the configuration's file holds. For each seed of
`--seeds`: the cell's runner is built at the cell's traffic, one verb
call scores the frame, and its outputs are judged (`sound`: the lower
reading of each limit is the largest of these). For each seed of
`--control-seeds` besides: the reference one step down in precision from
what the configuration states stands in the program's place
(`lm_latent_plants.CONTROLS`, by default `sums_128` alone; the upper
reading is the smallest of these). For each seed of `--exact-seeds`: the
sound outputs against the plain float32 reference along the same routing
(how far the stated precision is from exact arithmetic), not judged. For
each seed of `--fault-seeds`: each of `lm_latent_plants.FAULTS` planted in
a fresh runner, a short window measured as `perf/run.py` measures it, and
judged. `--rehearse` runs the rehearsal sizes on any backend.
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

import lm_latent_plants as plants  # noqa: E402
from lm_control_on_chip import judged  # noqa: E402
from perf.lib import harness  # noqa: E402

CELL = "joyai_score_32k"


def one_seed(cell, config, traffic, seed, rehearse, controls, exact, faults):
    import jax

    def build():
        env = harness.make_env(ROOT, cell, config, traffic, seed, rehearse)
        return env, harness.make_runner(env)

    env, runner = build()
    out = runner.issue()
    jax.block_until_ready(out)
    line = {"seed": seed, "rows": runner.check_rows,
            "sound": judged(*plants.judge_in_the_programs_place(runner, out))}
    got = [np.asarray(a)[runner.check_rows] for a in out]
    if exact:
        line["float32"] = env.reference.compare(
            got, runner.reference_rows(runner.check_rows, routing=got[2]),
            runner.model["num_experts_per_tok"])
    for name in controls:
        low = runner.reference_rows(
            runner.check_rows, **plants.CONTROLS[runner.model["dtype"]][name])
        line[name] = judged(*plants.judge_in_the_programs_place(runner, low))
    del runner, env, out
    gc.collect()
    for name in faults:
        env, runner = build()
        plants.FAULTS[name](env, runner)
        got = harness.measure(env, runner, 0.2 if rehearse else 6.0)
        line[name] = judged(*runner.check(), raised=got.summary["raised"])
        del runner, env, got
        gc.collect()
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="sums_128")
    ap.add_argument("--exact-seeds", default="")
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
    exact = set(ints(args.exact_seeds))
    seeds = ints(args.seeds)
    seeds += sorted((controls | faults | exact) - set(seeds))
    dtype = "float32" if args.rehearse else config["dtype"]
    names = [n for n in args.controls.split(",") if n in plants.CONTROLS[dtype]]
    if args.rehearse:
        names = sorted(plants.CONTROLS[dtype])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for seed in seeds:
        line = one_seed(cell, config, traffic, seed, args.rehearse,
                        names if seed in controls else [], seed in exact,
                        sorted(plants.FAULTS) if seed in faults else [])
        print(json.dumps(line), flush=True)
        with open(os.path.join(ROOT, "chiprun_out", "lm_latent_control.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
