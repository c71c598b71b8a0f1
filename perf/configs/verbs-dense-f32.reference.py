"""Plain reference of `verbs-dense-f32`: numpy, nothing of the package.

x + add on the integers the seed gives. Exact in float32 while every
value stays below 2**24, which `expected` asserts of itself.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from perf.lib import datagen  # noqa: E402

CHUNK = 1 << 20  # rows compared at a time: a few arrays that stay in cache
THREADS = 4      # numpy releases the lock; the window is closed by now


def expected(rows, seed, config):
    """float32 values of x + add at rows `rows`. An integer below 1024
    plus an integer below 2**24 adds exactly in float32."""
    add = float(config["add"])
    if add != int(add) or 1024 + add >= 2**24:
        raise ValueError("x + add leaves the integers float32 holds exactly")
    return datagen.rows_on_host(config["input"], rows, 1, seed) + np.float32(add)


def compare(final, nrows, seed, config):
    """(rows that differ, largest |difference|) over every row of one
    call's output `final`, read back once. A wrong length or type counts
    every row."""
    from concurrent.futures import ThreadPoolExecutor

    if tuple(final.shape) != (nrows,) or str(final.dtype) != config["dtype"]:
        return nrows, float("inf")
    got = np.asarray(final)

    def part(lo):
        hi = min(nrows, lo + CHUNK)
        want = expected(np.arange(lo, hi, dtype=np.uint32), seed, config)
        bad = got[lo:hi] != want  # a NaN differs
        n = int(np.count_nonzero(bad))
        if not n:
            return 0, 0.0
        d = np.abs(got[lo:hi][bad].astype(np.float64) - want[bad])
        return n, float("inf") if np.isnan(d).any() else float(d.max())

    with ThreadPoolExecutor(THREADS) as pool:
        parts = list(pool.map(part, range(0, nrows, CHUNK)))
    return sum(n for n, _ in parts), max((w for _, w in parts), default=0.0)
