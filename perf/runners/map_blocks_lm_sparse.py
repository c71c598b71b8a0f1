"""Runner `map_blocks_lm_sparse`: `map_blocks_lm`'s run (`tfs.map_blocks(fn,
frame, bindings={"params": tree})` of a `models.lm` scoring function over
one resident column of token ids, a row a window) for a configuration of
gated latent attention on a learned sparse index (`hy4-preview`), read
under its published key names, THIS CHIP's share of it as
`map_blocks_lm_hybrid` reads one (`held_experts`, `router_width`). The
weights come from `perf/lib/lm_weights_sparse.py`. The program gives a
fourth output, `index_choice` (the keys each query of a ``full`` layer
kept), and the check follows it through the reference beside the routing:
both decide what every later number of a row is.

A rehearsal runs the configuration's `presets.small` (the same code at toy
widths, a held half of the experts) over the traffic file's rehearsal
sizes."""

import numpy as np

from perf.lib import datagen, lm_weights_sparse
from perf.lib.sample import Reservoir
from perf.runners import map_blocks_lm
from perf.runners.map_blocks_lm_hybrid import model_config

OUTPUTS = map_blocks_lm.OUTPUTS + ("index_choice",)


class Runner(map_blocks_lm.Runner):
    def __init__(self, env):
        tfs, jax = env.tfs, env.jax
        from tensorframes_tpu.frame import Column
        from tensorframes_tpu.models import lm

        self.env, self.lm = env, lm
        self.model, self.held = model_config(env.config, env.rehearse)
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
        # 6 GiB of weights are made
        self.fn = lm.scoring_fn(self.model, held=self.held, interpret=bool(env.rehearse))
        tokens = map_blocks_lm.log_uniform_ids(
            jax, self.rows, self.seq, self.model["vocab_size"], env.seed
        )
        offsets = datagen.block_offsets(self.rows, int(env.traffic["blocks"]))
        self.frame = tfs.TensorFrame([Column("tokens", tokens)], offsets)
        # `weights` is what the reference is given, `program_params` the
        # same numbers as the timed path is bound to them (a test plants a
        # fault by altering the latter)
        self.weights = lm_weights_sparse.weights(self.model, env.seed, self.held)
        self.program_params = lm_weights_sparse.program_params(self.model, self.weights)
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

    def issue(self):
        out = self.lm.score(self.fn, self.frame, self.program_params, self.model)
        got = tuple(out[name].values for name in OUTPUTS)
        self.outputs.offer(got)  # judged once the window has closed
        return got

    def reference_rows(self, rows, routing=None, selection=None, **how):
        """The reference's (log-probabilities, loads, own top-k experts,
        own keys) of the frame's rows `rows`, a row at a time, given this
        chip's share; `routing` and `selection` (one entry a row of `rows`)
        are followed if given."""
        tokens = np.asarray(self.frame["tokens"].values[np.asarray(rows)])
        one = lambda a, i: None if a is None else np.asarray(a)[i:i + 1]
        parts = [
            self.env.reference.forward(
                self.model, self.weights, tokens[i:i + 1], held=self.held,
                routing=one(routing, i), selection=one(selection, i), **how)
            for i in range(len(rows))
        ]
        return tuple(np.concatenate([np.asarray(p[k]) for p in parts]) for k in range(4))

    def check(self):
        """The checked rows of every kept call's outputs against the
        reference of the same rows at the precision the configuration
        states, taken along the routing and the selection that call took."""
        outputs = self.outputs.drain()
        limits = self.model["limits"]
        rows = self.check_rows
        worst = {k: 0.0 for k in limits}
        wrong, want, followed = 0, None, None
        for k in sorted(outputs):
            got = [np.asarray(a) for a in outputs.pop(k)]
            if got[0].shape[:1] == (self.rows,):  # a whole call's outputs
                got = [a[rows] if a.shape[:1] == (self.rows,) else a for a in got]
            if want is None or not all(np.array_equal(a, b) for a, b in zip(got[2:], followed)):
                followed = got[2:]
                want = self.reference_rows(rows, routing=got[2], selection=got[3],
                                           operands=self.model["dtype"])
            read = self.env.reference.compare(got, want, self.model["num_experts_per_tok"])
            if not all(read[name] <= limits[name] for name in limits):
                wrong += 1
            for name in limits:
                if not read[name] <= worst[name]:
                    worst[name] = read[name]
        return {n: {"value": worst[n], "limit": limits[n]} for n in limits}, wrong
