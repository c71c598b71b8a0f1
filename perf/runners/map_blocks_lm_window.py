"""Runner `map_blocks_lm_window`: `map_blocks_lm`'s run (`tfs.map_blocks(fn,
frame, bindings={"params": tree})` of a `models.lm` scoring function over
one resident column of token ids, a row a window) for a configuration of
sliding-window and full attention mixed (`trinity-mini`), read under its
published key names; every expert is held here. The weights come from
`perf/lib/lm_weights_window.py`; issue and check are `map_blocks_lm`'s own
(the check follows the routing the call took through the reference).

A rehearsal runs the configuration's `presets.small` (the same code at toy
widths) over the traffic file's rehearsal sizes."""

import numpy as np

from perf.lib import datagen, lm_weights_window
from perf.lib.sample import Reservoir
from perf.runners import map_blocks_lm


class Runner(map_blocks_lm.Runner):
    def __init__(self, env):
        tfs, jax = env.tfs, env.jax
        from tensorframes_tpu.frame import Column
        from tensorframes_tpu.models import lm

        self.env, self.lm = env, lm
        self.model = map_blocks_lm.model_config(env.config, env.rehearse)
        self.rows = int(env.traffic["rows"])
        self.seq = int(env.traffic["seq"])
        if self.seq != self.model["score_window"]:
            raise ValueError(
                f"traffic scores windows of {self.seq} tokens, the "
                f"configuration counts work for {self.model['score_window']}"
            )
        self.rows_per_call = self.rows
        # the kernels are compiled for the chip; only a rehearsal (any
        # backend, never a measurement) interprets them. Built first: a
        # package that cannot plan this family's layers raises here, before
        # 7.9 GiB of weights are made
        self.fn = lm.scoring_fn(self.model, interpret=bool(env.rehearse))
        tokens = map_blocks_lm.log_uniform_ids(
            jax, self.rows, self.seq, self.model["vocab_size"], env.seed
        )
        offsets = datagen.block_offsets(self.rows, int(env.traffic["blocks"]))
        self.frame = tfs.TensorFrame([Column("tokens", tokens)], offsets)
        # `weights` is what the reference is given, `program_params` the
        # same numbers as the timed path is bound to them (a test plants a
        # fault by altering the latter)
        self.weights = lm_weights_window.weights(self.model, env.seed)
        self.program_params = lm_weights_window.program_params(self.model, self.weights)
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
