"""Runner `map_blocks_lm`: `tfs.map_blocks(fn, frame, bindings={"params":
tree})` of a `models.lm` scoring function over one resident column of
token ids, a row a window of the corpus. The weights are a pytree of
device arrays bound to the verb; every call scores the same frame. The
weights are the benchmark's (`perf/lib/lm_weights.py`): made from the
seed in the reference's layout, given to the reference as they are and
written into the program's stacked pytree here.

A rehearsal runs the configuration's `presets.small` (the same code at
toy widths) over the traffic file's rehearsal sizes."""

import numpy as np

from perf.lib import datagen, lm_weights
from perf.lib.sample import Reservoir

OUTPUTS = ("token_logprob", "expert_load", "expert_choice")


def model_config(config, rehearse):
    """The configuration as run: the file's keys, under a rehearsal with
    its small preset laid over them."""
    return {**config, **config["presets"]["small"]} if rehearse else dict(config)


def log_uniform_ids(jax, rows, seq, vocab, seed):
    """(rows, seq) int32 ids, floor(vocab**u) - 1 clipped to the
    vocabulary, u in [0, 1) the 32-bit hash of the flat index and the
    seed: frequent ids repeat, as in text. One jitted call."""
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        idx = jnp.arange(rows * seq, dtype=jnp.uint32)
        u = datagen.mix32(idx, key).astype(jnp.float32) * jnp.float32(2.0 ** -32)
        ids = jnp.floor(jnp.exp2(u * jnp.float32(np.log2(vocab)))).astype(jnp.int32) - 1
        return jnp.clip(ids, 0, vocab - 1).reshape(rows, seq)

    return make(jnp.asarray(datagen.seed_word(seed), dtype=jnp.uint32))


class Runner:
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
        tokens = log_uniform_ids(
            jax, self.rows, self.seq, self.model["vocab_size"], env.seed
        )
        offsets = datagen.block_offsets(self.rows, int(env.traffic["blocks"]))
        self.frame = tfs.TensorFrame([Column("tokens", tokens)], offsets)
        # `weights` is what the reference is given, `program_params` the
        # same numbers as the timed path is bound to them (a test plants a
        # fault by altering the latter)
        self.weights = lm_weights.weights(self.model, env.seed)
        self.program_params = lm_weights.program_params(self.model, self.weights)
        jax.block_until_ready((tokens, self.program_params))
        # the attention kernel is compiled for the chip; only a rehearsal
        # (any backend, never a measurement) interprets it
        self.fn = lm.scoring_fn(self.model, interpret=bool(env.rehearse))
        self.pick = np.random.RandomState(int(datagen.seed_word(env.seed)))
        self.outputs = Reservoir(int(env.traffic.get("kept_outputs", 2)), self.pick)
        # the rows `check` compares: row 0 and others drawn from the seed
        n = min(int(self.model["check_rows"]), self.rows)
        self.check_rows = [0]
        while len(self.check_rows) < n:
            r = int(self.pick.randint(1, self.rows))
            if r not in self.check_rows:
                self.check_rows.append(r)

    def start_window(self):
        self.outputs.reset()

    def issue(self):
        out = self.lm.score(self.fn, self.frame, self.program_params, self.model)
        got = tuple(out[name].values for name in OUTPUTS)
        self.outputs.offer(got)  # judged once the window has closed
        return got

    def reference_rows(self, rows, routing=None, **how):
        """The reference's (log-probabilities, loads, own top-k) of the
        frame's rows `rows`, a row at a time so that it fits beside the
        weights; `routing` (one entry a row of `rows`) is followed if given."""
        tokens = np.asarray(self.frame["tokens"].values[np.asarray(rows)])
        parts = [
            self.env.reference.forward(
                self.model, self.weights, tokens[i:i + 1],
                routing=None if routing is None else routing[i:i + 1], **how)
            for i in range(len(rows))
        ]
        return tuple(np.concatenate([np.asarray(p[k]) for p in parts]) for k in range(3))

    def check(self):
        """Rows 0 and one drawn from the seed of every kept call's
        outputs against the reference of the same rows at the precision
        the configuration states, taken along the routing that call took."""
        outputs = self.outputs.drain()
        limits = self.model["limits"]
        rows = self.check_rows
        worst = {k: 0.0 for k in limits}
        wrong, want, followed = 0, None, None
        for k in sorted(outputs):
            got = [np.asarray(a) for a in outputs.pop(k)]
            if got[0].shape[:1] == (self.rows,):  # a whole call's outputs
                got = [a[rows] if a.shape[:1] == (self.rows,) else a for a in got]
            if want is None or not np.array_equal(got[2], followed):
                followed = got[2]
                want = self.reference_rows(
                    rows, routing=followed, operands=self.model["dtype"])
            read = self.env.reference.compare(
                got, want, self.model["num_experts_per_tok"])
            if not all(read[name] <= limits[name] for name in limits):
                wrong += 1
            for name in limits:
                if not read[name] <= worst[name]:
                    worst[name] = read[name]
        return {n: {"value": worst[n], "limit": limits[n]} for n in limits}, wrong
