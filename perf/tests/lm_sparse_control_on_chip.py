"""Readings for the limits of `hy4_score_16k`'s `correct`, on the chip at
the cell's own size; not part of a benchmark run and not a pytest file.

    python3 perf/tests/lm_sparse_control_on_chip.py --seeds 1,2,... \
        --control-seeds 101,... --fault-seeds 201

`lm_latent_control_on_chip.py`'s readings and arguments (sound runs, the
reference one step down in the program's place, planted faults;
`--rehearse`), for the sparse-attention cell and its plants
(`lm_sparse_plants.py`: dense attention in place of the selection, a shared
layer that reselects, a plain residual in place of the hyper-connections,
a held expert left out). That script reads its cell and its plants from
two names of its own module: this one sets them and runs it, so the JSON
lines are also appended under ITS file name,
`chiprun_out/lm_latent_control.jsonl`. `--exact-seeds` is not for this
cell (that reading would follow the routing and not the selection)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lm_latent_control_on_chip as control  # noqa: E402
import lm_sparse_plants  # noqa: E402

control.CELL = "hy4_score_16k"
control.plants = lm_sparse_plants

if __name__ == "__main__":
    control.main()
