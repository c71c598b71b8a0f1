"""What the algorithm needs, from shapes: bytes and FLOPs per real row.

Pad rows, copies and the extra matmul passes of float32 are not work.
One function per runner; `least_seconds` turns rows into the least time
a chip could take and says which peak bounds it.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in perf/lib/peaks.json"
        )
    return table[device_kind]


def map_chain(config: dict) -> dict:
    """One elementwise pass: read a cell, write a cell."""
    itemsize = {"float32": 4}[config["dtype"]]
    return {"bytes_per_row": 2 * itemsize, "flops_per_row": 1}


def map_rows_mlp(config: dict) -> dict:
    """Dense layers: 2·fan_in·fan_out FLOP a row; the row read and the
    probabilities written."""
    sizes = config["layer_sizes"]
    itemsize = {"float32": 4}[config["dtype"]]
    flops = sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return {
        "bytes_per_row": itemsize * (sizes[0] + sizes[-1]),
        "flops_per_row": flops,
    }


def for_runner(runner: str, config: dict) -> dict:
    """The function above named as the runner; a later runner brings
    `perf/lib/work_<runner>.py` with a `work(config)` of its own."""
    fn = globals().get(runner)
    if fn is None:
        import importlib

        fn = importlib.import_module(f"perf.lib.work_{runner}").work
    return fn(config)


def least_seconds(rows: float, work: dict, peaks: dict):
    """(seconds, bound): the larger of bytes over the HBM peak and FLOPs
    over the bf16 peak, for `rows` real rows on one chip."""
    by_hbm = rows * work["bytes_per_row"] / peaks["hbm_bytes_per_s"]
    by_mxu = rows * work["flops_per_row"] / peaks["bf16_flops_per_s"]
    return (by_hbm, "hbm") if by_hbm >= by_mxu else (by_mxu, "mxu")
