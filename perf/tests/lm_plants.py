"""What `lfm2_score_4k`'s `correct` has to refuse, planted in a built
runner: three faults in the timed path, and the control, the reference
run one step below the precision the configuration states standing in the
program's place. `test_lm_cell.py` plants them at the rehearsal's sizes,
`lm_control_on_chip.py` at the cell's own."""


def expert_left_out(env, runner):
    """One expert's output is left out of every expert layer."""
    p = runner.program_params
    down = p["moe"]["w_down"].at[:, 3].set(0.0)
    runner.program_params = {**p, "moe": {**p["moe"], "w_down": down}}


def conv_tap_left_out(env, runner):
    """The convolution forgets z_{t-2}."""
    p = runner.program_params
    taps = p["conv"]["taps"].at[:, 2].set(0.0)
    runner.program_params = {**p, "conv": {**p["conv"], "taps": taps}}


def load_of_another_row(env, runner):
    """Every row reports its neighbour's expert_load."""
    import jax.numpy as jnp

    real = runner.lm.score

    def score(*a, **k):
        out = real(*a, **k)
        cols = [c if c.name != "expert_load" else type(c)(
            "expert_load", jnp.roll(c.values, 1, axis=0)) for c in
            (out[n] for n in out.columns)]
        return env.tfs.TensorFrame(cols, out.offsets)

    runner.lm = type("planted", (), {"score": staticmethod(score)})


FAULTS = {"expert_left_out": expert_left_out, "conv_tap_left_out": conv_tap_left_out,
          "load_of_another_row": load_of_another_row}

# one step below the configuration's precision: below float32 (the
# rehearsal's preset) bfloat16 operands; below bfloat16 operands with
# float32 sums, the expert matmuls' running sums kept in bfloat16 and
# rounded after every `expert_sum_chunk` products (128: one pass of a
# matrix unit, what a grouped matmul with a bfloat16 output does)
CONTROLS = {
    "float32": {"operands": {"operands": "bfloat16"},
                "operands_and_sums": {"operands": "bfloat16", "expert_sum_chunk": 8}},
    "bfloat16": {"sums_128": {"operands": "bfloat16", "expert_sum_chunk": 128},
                 "sums_8": {"operands": "bfloat16", "expert_sum_chunk": 8}},
}


def judge_in_the_programs_place(runner, outputs):
    """`runner.check` on `outputs` (log-probabilities, loads, choices of
    the checked rows) as if a timed call had produced them."""
    runner.start_window()
    runner.outputs.offer(outputs)
    return runner.check()


def control(runner, how):
    """(compared, wrong) with the reference run as `how` says, along its
    own routing, in the program's place."""
    low = runner.reference_rows(runner.check_rows, **how)
    return judge_in_the_programs_place(runner, low)
