"""What `joyai_score_32k`'s `correct` has to refuse, planted in a built
runner: three faults in the timed path, and the control, the reference
run one step below the precision the configuration states standing in the
program's place. `test_lm_latent_cell.py` plants them at the rehearsal's
sizes, `lm_latent_control_on_chip.py` at the cell's own. The plants that
do not depend on the family are `lm_plants.py`'s."""

from lm_plants import (control, expert_left_out,  # noqa: F401
                       judge_in_the_programs_place, load_of_another_row)


def rope_key_left_out(env, runner):
    """The rotary key all heads share is left out of the score: k_r = 0,
    so q_r k_r^T adds nothing."""
    p = runner.program_params
    rkv = int(runner.model["kv_lora_rank"])
    w_kva = p["mla"]["w_kva"].at[:, :, rkv:].set(0.0)
    runner.program_params = {**p, "mla": {**p["mla"], "w_kva": w_kva}}


FAULTS = {"expert_left_out": expert_left_out, "rope_key_left_out": rope_key_left_out,
          "load_of_another_row": load_of_another_row}

# one step below the configuration's precision: below float32 (the
# rehearsal's preset) bfloat16 operands; below bfloat16 operands with
# float32 sums, the running sums of the expert matmuls and of the
# attention's two products kept in bfloat16 and rounded after every
# `sum_chunk` products (128: one pass of a matrix unit)
CONTROLS = {
    "float32": {"operands": {"operands": "bfloat16"},
                "operands_and_sums": {"operands": "bfloat16", "sum_chunk": 8}},
    "bfloat16": {"sums_128": {"operands": "bfloat16", "sum_chunk": 128},
                 "sums_8": {"operands": "bfloat16", "sum_chunk": 8}},
}
