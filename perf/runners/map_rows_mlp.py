"""Runner `map_rows_mlp`: `tfs.map_rows` of a frozen `models.MLP`
scoring graph over one resident float32 feature column (BASELINE config
3, chip_smoke.py phase e). Every call scores the same frame."""

import numpy as np

from perf.lib import datagen
from perf.lib.sample import Reservoir


class Runner:
    def __init__(self, env):
        tfs, jax = env.tfs, env.jax
        from tensorframes_tpu.frame import Column
        from tensorframes_tpu.models import MLP

        self.env = env
        self.rows = int(env.traffic["rows"])
        self.rows_per_call = self.rows
        sizes = list(env.config["layer_sizes"])
        blocks = int(env.traffic["blocks"])
        offsets = datagen.block_offsets(self.rows, blocks)
        x = datagen.on_device(jax, env.config["input"], (self.rows, sizes[0]), env.seed)
        jax.block_until_ready(x)
        self.frame = tfs.TensorFrame([Column("features", x)], offsets)
        # the weights are the benchmark's, from the seed; the model class
        # only freezes them into its scoring graph
        self.params = env.reference.make_params(sizes, env.seed)
        model = MLP(sizes, seed=0)
        model.params = [(w, b) for w, b in self.params]
        self.graph = model.scoring_graph("features", block=False)
        self.pick = np.random.RandomState(int(datagen.seed_word(env.seed)))
        self.outputs = Reservoir(int(env.traffic.get("kept_outputs", 3)), self.pick)

    def start_window(self):
        self.outputs.reset()

    def issue(self):
        out = self.env.tfs.map_rows(self.graph, self.frame)
        probs = out["probs"].values
        self.outputs.offer(probs)  # judged once the window has closed
        return probs

    def check(self):
        """Sampled rows (from the seed, the first and the last among
        them) of the kept calls' outputs against the float64 reference."""
        env = self.env
        outputs = self.outputs.drain()
        self.frame = None
        n = min(int(env.config["check_rows"]), self.rows)
        sample = np.unique(np.concatenate([
            [0, self.rows - 1], self.pick.randint(0, self.rows, size=n)
        ])).astype(np.int64)
        sizes = env.config["layer_sizes"]
        feats = datagen.rows_on_host(env.config["input"], sample, sizes[0], env.seed)
        want = env.reference.forward(feats, self.params)
        limit = env.config["limits"]["probs_max_abs_err"]
        worst, wrong = 0.0, 0
        for k in sorted(outputs):
            got = np.asarray(outputs.pop(k)[sample])
            if got.shape != want.shape:
                err = float("inf")
            else:
                err = float(np.max(np.abs(got.astype(np.float64) - want)))
            if not err <= limit:
                wrong += 1
            worst = err if not err <= worst else worst
        return {"probs_max_abs_err": {"value": worst, "limit": limit}}, wrong
