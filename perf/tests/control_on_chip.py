"""Readings for the limits of `correct`, on the chip at the cell's own
size; not part of a benchmark run and not a pytest file.

    python3 perf/tests/control_on_chip.py --workload <cell> \
        --seeds 1,2,3,... --control-seeds 101,102,103 --seconds 2

For each seed one JSON line: the numbers a sound run compares (the lower
reading is their largest), and for each control seed the numbers of the
control (the upper reading is their smallest). The control is one step
down in precision from what the configuration states:

- `mlp-512-scoring` (float32 at `highest`): the program with its own
  path switched on, `matmul_precision="tensorfloat32"`, which is
  `lax.Precision.HIGH`, three bfloat16 passes;
- `verbs-dense-f32` (float32, no precision knob): the reference's x + add
  computed in bfloat16 on the device in the verb's place.

All seeds run in one process: set-up is most of a run.
"""

import argparse
import contextlib
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.lib import harness  # noqa: E402


def bfloat16_in_place_of(tfs, config):
    """The control of verbs-dense-f32: the verb call replaced by the
    reference's arithmetic in bfloat16."""
    import jax.numpy as jnp

    from tensorframes_tpu.frame import Column

    real = tfs.map_blocks

    def verb(fetch, frame, *a, **k):
        x = frame["x"].values
        z = (x.astype(jnp.bfloat16) + jnp.bfloat16(config["add"])).astype(x.dtype)
        return tfs.TensorFrame([Column("z", z), frame["x"]], frame.offsets)

    @contextlib.contextmanager
    def planted():
        tfs.map_blocks = verb
        try:
            yield
        finally:
            tfs.map_blocks = real
    return planted()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()

    import jax

    if jax.local_devices()[0].platform != "tpu":
        print("control_on_chip.py needs the TPU", file=sys.stderr)
        return 1
    import tensorframes_tpu as tfs

    tfs.config.enable_compilation_cache()
    _, cell, config, traffic = harness.load_cell(ROOT, args.workload)

    def one(seed, control):
        env = harness.make_env(ROOT, cell, config, traffic, seed)
        if not control:
            how = contextlib.nullcontext()
        elif config["runner"] == "map_rows_mlp":
            how = tfs.config.override(matmul_precision="tensorfloat32")
        else:
            how = bfloat16_in_place_of(tfs, config)
        with how:
            runner = harness.make_runner(env)
            got = harness.measure(env, runner, args.seconds)
        compared, wrong = runner.check()
        calls, raised = got.summary["attempted"], got.summary["raised"]
        for c in got.calls:
            if c.error is not None:
                print(f"seed {seed}: a call raised: {c.error[:800]}", file=sys.stderr)
                break
        # every verb call leaves a reference cycle that holds its input
        # frame (PERF.md, Open questions 1): many seeds in one process need
        # the collector between them, which one benchmark run does not
        del runner, got
        gc.collect()
        print(json.dumps({
            "workload": cell["name"], "seed": seed,
            "kind": "control" if control else "sound",
            "calls": calls, "raised": raised, "wrong_calls": wrong,
            "correct": harness.decide(compared, raised),
            "compared": compared,
        }), flush=True)

    # the control first, on seeds of its own: a program traced at the
    # lower precision is cached by its graph, and a seed is a new graph
    for s in [int(x) for x in args.control_seeds.split(",") if x]:
        one(s, control=True)
    for s in [int(x) for x in args.seeds.split(",") if x]:
        one(s, control=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
