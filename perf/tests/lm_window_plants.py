"""What `trinity_score_32k`'s `correct` has to refuse, planted in a built
runner: faults in the timed path, and the control, the reference run one
step below the precision the configuration states standing in the
program's place. `test_lm_window_cell.py` plants them at the rehearsal's
sizes, `lm_window_control_on_chip.py` at the cell's own (there `FAULTS`
alone, the window's control; `BROKEN` are judged at the rehearsal's size).

`window_one_key_wider` is the control of the window itself: the program
run with a band one key wider than the configuration's (query t also sees
key t - W), the off-by-one a window's code is most likely to have."""

from lm_plants import control, expert_left_out, judge_in_the_programs_place  # noqa: F401


def window_one_key_wider(env, runner):
    """The program's sliding layers attend to the last W + 1 keys."""
    model = dict(runner.model, sliding_window=int(runner.model["sliding_window"]) + 1)
    runner.fn = runner.lm.scoring_fn(model, interpret=bool(env.rehearse))


def _outputs_altered(runner, alter, after=0):
    """Every verb call from the `after`-th on hands back its outputs as
    `alter(outputs, frame)` makes them (columns by name)."""
    real, n = runner.lm.score, [0]

    def score(fn, frame, *a, **k):
        out = real(fn, frame, *a, **k)
        n[0] += 1
        if n[0] <= after:
            return out
        cols = alter({c: out[c].values for c in out.columns}, frame)
        return type(frame)([type(out[c])(c, cols[c]) for c in out.columns], out.offsets)

    runner.lm = type("planted", (), {"score": staticmethod(score)})


def state_unchanged(env, runner):
    """After a few sound calls, the call hands its input back: the token
    ids stand where the log-probabilities should be."""
    import jax.numpy as jnp

    _outputs_altered(runner, lambda o, frame: dict(
        o, token_logprob=frame["tokens"].values.astype(jnp.float32)), after=5)


def half_left_out(env, runner):
    """The second half of the rows is not computed: zeros stand there."""
    def alter(o, frame):
        half = frame.nrows // 2
        return {k: v.at[half:].set(0) for k, v in o.items()}

    _outputs_altered(runner, alter)


def one_answer_altered(env, runner):
    """One count of one call's `expert_load` is one off (the loads are
    answers the configuration guarantees exactly; one log-probability off
    by 1e-3 is inside what the 99th percentile sees)."""
    _outputs_altered(runner, lambda o, frame: dict(
        o, expert_load=o["expert_load"].at[-1, 0, 0].add(1)), after=4)


FAULTS = {"window_one_key_wider": window_one_key_wider}
BROKEN = {"state_unchanged": state_unchanged, "half_left_out": half_left_out,
          "one_answer_altered": one_answer_altered, "expert_left_out": expert_left_out}

# one step below the configuration's precision: below float32 (the
# rehearsal's preset) bfloat16 operands; below bfloat16 operands with
# float32 sums, the running sums of the held experts' matmuls kept in
# bfloat16 and rounded after every `sum_chunk` products (128: one pass of a
# matrix unit, what a grouped matmul with a bfloat16 output does)
CONTROLS = {
    "float32": {"operands": {"operands": "bfloat16"},
                "operands_and_sums": {"operands": "bfloat16", "sum_chunk": 8}},
    "bfloat16": {"sums_128": {"operands": "bfloat16", "sum_chunk": 128},
                 "sums_8": {"operands": "bfloat16", "sum_chunk": 8}},
}
