"""Readings for the limits of `trinity_score_32k`'s `correct`, on the chip at
the cell's own size; not part of a benchmark run and not a pytest file.

    python3 perf/tests/lm_window_control_on_chip.py --seeds 1,2,... \
        --control-seeds 101,... --fault-seeds 201

`lm_latent_control_on_chip.py`'s readings and arguments (sound runs, the
reference one step down in the program's place, planted faults, the plain
float32 reference for `--exact-seeds`; `--rehearse`), for the
sliding-window cell and its plants (`lm_window_plants.py`: the program's
window one key wider). That script reads its cell
and its plants from two names of its own module: this one sets them and
runs it, so the JSON lines are also appended under ITS file name,
`lm_latent_control.jsonl` in the output directory."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lm_latent_control_on_chip as control  # noqa: E402
import lm_window_plants  # noqa: E402

control.CELL = "trinity_score_32k"
control.plants = lm_window_plants

if __name__ == "__main__":
    control.main()
