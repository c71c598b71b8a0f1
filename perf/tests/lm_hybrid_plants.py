"""What `nemotron3_score_32k`'s `correct` has to refuse, planted in a built
runner: three faults in the timed path, and the control, the reference
run one step below the precision the configuration states standing in the
program's place. `test_lm_hybrid_cell.py` plants them at the rehearsal's
sizes, `lm_hybrid_control_on_chip.py` at the cell's own. The plants that
do not depend on the family are `lm_plants.py`'s."""

from lm_plants import (control, expert_left_out,  # noqa: F401
                       judge_in_the_programs_place, load_of_another_row)


def state_not_handed_over(env, runner):
    """At the chunk boundary in the middle of the window the scan starts
    again from an empty state: the two halves are scanned apart."""
    import jax.numpy as jnp

    lm = runner.lm
    real = lm.ssd_scan

    def halves(x, dt, A, B, C, D, *, chunk, interpret):
        cut = max(chunk, x.shape[1] // 2 // chunk * chunk)
        return jnp.concatenate([
            real(x[:, a:b], dt[:, a:b], A, B[:, a:b], C[:, a:b], D,
                 chunk=chunk, interpret=interpret)
            for a, b in ((0, cut), (cut, x.shape[1]))], axis=1)

    sound = lm.scoring_fn(runner.model, held=runner.held, interpret=bool(env.rehearse))

    def lm_score(tokens, params):  # the name the program's module takes
        lm.ssd_scan = halves
        try:
            return sound(tokens, params)
        finally:
            lm.ssd_scan = real

    runner.fn = lm_score


FAULTS = {"expert_left_out": expert_left_out,
          "state_not_handed_over": state_not_handed_over,
          "load_of_another_row": load_of_another_row}

# one step below the configuration's precision: below float32 (the
# rehearsal's preset) bfloat16 operands; below bfloat16 operands with
# float32 sums, the running sums of the expert matmuls kept in bfloat16
# and rounded after every `sum_chunk` products (128: one pass of a matrix
# unit), and the scan's carried state rounded after every `sum_chunk`
# positions
CONTROLS = {
    "float32": {"operands": {"operands": "bfloat16"},
                "operands_and_sums": {"operands": "bfloat16", "sum_chunk": 8}},
    "bfloat16": {"sums_128": {"operands": "bfloat16", "sum_chunk": 128},
                 "sums_8": {"operands": "bfloat16", "sum_chunk": 8}},
}
