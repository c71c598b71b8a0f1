"""What `hy4_score_16k`'s `correct` has to refuse, planted in a built
runner: four faults in the timed path, and the control, the reference run
one step below the precision the configuration states standing in the
program's place. `test_lm_sparse_cell.py` plants them at the rehearsal's
sizes, `lm_sparse_control_on_chip.py` at the cell's own. The plants that do
not depend on the family are `lm_plants.py`'s."""

import contextlib

from lm_plants import control, expert_left_out, judge_in_the_programs_place  # noqa: F401


def _traced_with(runner, env, name, stand_in):
    """The runner's function traced with `models.lm`'s `name` replaced by
    `stand_in(real)` (the program's module keeps its name, `jit_lm_score`)."""
    lm = runner.lm
    real = getattr(lm, name)
    sound = lm.scoring_fn(runner.model, held=runner.held, interpret=bool(env.rehearse))

    @contextlib.contextmanager
    def planted():
        setattr(lm, name, stand_in(real))
        try:
            yield
        finally:
            setattr(lm, name, real)

    def lm_score(tokens, params):  # the name the program's module takes
        with planted():
            return sound(tokens, params)

    runner.fn = lm_score


def dense_attention(env, runner):
    """Every query attends to every key up to it, not to its selection
    (the indexer still runs, and `index_choice` still names its keys)."""
    import jax.numpy as jnp

    def stand_in(real):
        def every_key(q, k, v, selection, sink, **kw):
            causal = jnp.tril(jnp.ones(selection.shape[-2:], jnp.int8))
            return real(q, k, v, jnp.broadcast_to(causal, selection.shape), sink, **kw)
        return every_key

    _traced_with(runner, env, "sparse_attention", stand_in)


def shared_layer_reselects(env, runner):
    """A shared layer chooses its keys again (with the last full layer's
    indexer, from its own input), where it should take that layer's."""
    import jax.numpy as jnp

    last = sum(t == "full" for t in runner.model["indexer_types"]) - 1

    def stand_in(real):
        def reselect(config, p, index_p, u, carried, index_at, interpret):
            at = jnp.where(index_at >= 0, index_at, jnp.int32(last))
            return real(config, p, index_p, u, carried, at, interpret)
        return reselect

    _traced_with(runner, env, "_sparse_attention_op", stand_in)


def plain_residual(env, runner):
    """One residual stream, ``h + F(RMSNorm(h))``: the hyper-connections
    left out."""
    _traced_with(runner, env, "_hc_width", lambda real: lambda config: 0)


FAULTS = {"dense_attention": dense_attention, "shared_layer_reselects": shared_layer_reselects,
          "plain_residual": plain_residual, "expert_left_out": expert_left_out}

# one step below the configuration's precision: below float32 (the
# rehearsal's preset) bfloat16 operands; below bfloat16 operands with
# float32 sums, the running sums of the indexer's scores, of the
# attention's two products and of the held experts' matmuls kept in
# bfloat16 and rounded after every `sum_chunk` products (128: one pass of a
# matrix unit)
CONTROLS = {
    "float32": {"operands": {"operands": "bfloat16"},
                "operands_and_sums": {"operands": "bfloat16", "sum_chunk": 8}},
    "bfloat16": {"sums_128": {"operands": "bfloat16", "sum_chunk": 128},
                 "sums_8": {"operands": "bfloat16", "sum_chunk": 8}},
}
