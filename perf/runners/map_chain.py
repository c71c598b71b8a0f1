"""Runner `map_chain`: `tfs.map_blocks` of "x + add" over one dense
float32 column (chip_smoke.py phase b, bench.py) on a frame cut into the
traffic's blocks. Every call maps the same resident frame (a chain of
calls, each on the last one's output, runs out of device memory: PERF.md,
Open questions 1); a sample of the calls' outputs, drawn from the seed,
is kept whole for the check."""

import numpy as np

from perf.lib import datagen
from perf.lib.sample import Reservoir


class Runner:
    def __init__(self, env):
        tfs, jax = env.tfs, env.jax
        from tensorframes_tpu.frame import Column

        self.env = env
        self.rows = int(env.traffic["rows"])
        self.rows_per_call = self.rows
        offsets = datagen.block_offsets(self.rows, int(env.traffic["blocks"]))
        x = datagen.on_device(jax, env.config["input"], (self.rows,), env.seed)
        jax.block_until_ready(x)
        self.frame = tfs.TensorFrame([Column("x", x)], offsets)
        self.fetch = (tfs.block(self.frame, "x") + float(env.config["add"])).named("z")
        self.outputs = Reservoir(
            int(env.traffic["kept_outputs"]),
            np.random.RandomState(int(datagen.seed_word(env.seed))),
        )

    def issue(self):
        z = self.env.tfs.map_blocks(self.fetch, self.frame)["z"].values
        self.outputs.offer(z)  # judged once the window has closed
        return z

    def start_window(self):
        self.outputs.reset()

    def check(self):
        """Every row of each kept output of the window, the last call's
        among them, against the reference."""
        env, limits = self.env, self.env.config["limits"]
        kept = self.outputs.drain()
        self.frame = None
        mismatched, worst, wrong = 0, 0.0, 0
        for k in sorted(kept):
            bad, far = env.reference.compare(kept.pop(k), self.rows, env.seed, env.config)
            mismatched += bad
            worst = max(worst, far)
            wrong += bad > limits["rows_mismatched"]
        return {
            "rows_mismatched": {"value": mismatched, "limit": limits["rows_mismatched"]},
            "max_abs_diff": {"value": worst, "limit": limits["max_abs_diff"]},
        }, wrong
