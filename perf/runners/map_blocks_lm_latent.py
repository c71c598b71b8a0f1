"""Runner `map_blocks_lm_latent`: `map_blocks_lm`'s run (`tfs.map_blocks(fn,
frame, bindings={"params": tree})` of a `models.lm` scoring function over
one resident column of token ids, a row a window) for a configuration of
the latent-attention family, read under its published key names. What
differs from `map_blocks_lm` is how the runner is built: the weights come
from `perf/lib/lm_weights_latent.py` (this family's reference layout),
and the configuration's `derived` keys, which stand in the file for the
metric readers written for the other family's names, are taken off before
the program and the reference see it. Issue, reference and check are
`map_blocks_lm`'s own.

A rehearsal runs the configuration's `presets.small` (the same code at
toy widths) over the traffic file's rehearsal sizes."""

import numpy as np

from perf.lib import datagen, lm_weights_latent
from perf.lib.sample import Reservoir
from perf.runners import map_blocks_lm


def model_config(config, rehearse):
    """The configuration as run: the file's published keys (not the
    `derived` ones), under a rehearsal with its small preset laid over them."""
    derived = set(config.get("derived", ()))
    model = {k: v for k, v in config.items() if k not in derived}
    return {**model, **config["presets"]["small"]} if rehearse else model


class Runner(map_blocks_lm.Runner):
    def __init__(self, env):
        tfs, jax = env.tfs, env.jax
        from tensorframes_tpu.frame import Column
        from tensorframes_tpu.models import lm

        self.env, self.lm = env, lm
        self.model = model_config(env.config, env.rehearse)
        self.rows = int(env.traffic["rows"])
        self.seq = int(env.traffic["seq"])
        if self.seq != self.model["score_window"]:
            raise ValueError(
                f"traffic scores windows of {self.seq} tokens, the "
                f"configuration counts work for {self.model['score_window']}"
            )
        self.rows_per_call = self.rows
        # the attention kernel is compiled for the chip; only a rehearsal
        # (any backend, never a measurement) interprets it. Built first: a
        # package that cannot plan this family's layers raises here, before
        # 10 GiB of weights are made
        self.fn = lm.scoring_fn(self.model, interpret=bool(env.rehearse))
        tokens = map_blocks_lm.log_uniform_ids(
            jax, self.rows, self.seq, self.model["vocab_size"], env.seed
        )
        offsets = datagen.block_offsets(self.rows, int(env.traffic["blocks"]))
        self.frame = tfs.TensorFrame([Column("tokens", tokens)], offsets)
        # `weights` is what the reference is given, `program_params` the
        # same numbers as the timed path is bound to them (a test plants a
        # fault by altering the latter)
        self.weights = lm_weights_latent.weights(self.model, env.seed)
        self.program_params = lm_weights_latent.program_params(self.model, self.weights)
        jax.block_until_ready((tokens, self.program_params))
        self.pick = np.random.RandomState(int(datagen.seed_word(env.seed)))
        self.outputs = Reservoir(int(env.traffic.get("kept_outputs", 2)), self.pick)
        # the rows `check` compares: row 0 and others drawn from the seed
        n = min(int(self.model["check_rows"]), self.rows)
        self.check_rows = [0]
        while len(self.check_rows) < n:
            r = int(self.pick.randint(1, self.rows))
            if r not in self.check_rows:
                self.check_rows.append(r)
