"""Readings for the limits of `nemotron3_score_32k`'s `correct`, on the chip
at the cell's own size; not part of a benchmark run and not a pytest file.

    python3 perf/tests/lm_hybrid_control_on_chip.py --seeds 1,2,... \
        --control-seeds 101,... --fault-seeds 201 [--exact-seeds 1]

`lm_latent_control_on_chip.py`'s readings and arguments (sound runs, the
reference one step down in the program's place, the float32 reference
along the same routing, planted faults; `--rehearse`), for the hybrid
state-space cell and its plants (`lm_hybrid_plants.py`: a held expert left
out, the scan's state not handed over at one chunk boundary, another row's
`expert_load`). That script reads its cell and its plants from two names of
its own module: this one sets them and runs it, so the JSON lines are also
appended under ITS file name, `chiprun_out/lm_latent_control.jsonl`. A
reference pass over two 32,768-token rows (every one of the 128 held
experts on every token, the scans one position at a time) takes 45 s."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lm_hybrid_plants  # noqa: E402
import lm_latent_control_on_chip as control  # noqa: E402

control.CELL = "nemotron3_score_32k"
control.plants = lm_hybrid_plants

if __name__ == "__main__":
    control.main()
