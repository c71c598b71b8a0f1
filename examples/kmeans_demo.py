"""Distributed k-means: the reference's flagship demo, TPU-native.

Mirrors `tensorframes_snippets/kmeans_demo.py` (per-block assignment +
`unsorted_segment_sum` partials inside a trimmed map, then a cross-block
combine) with the TPU execution model: the assignment graph compiles to
ONE XLA executable (centers are a bound placeholder — a jit argument —
so Lloyd iterations never recompile), blocks shard over the device mesh
when one is given, and the combine is a tiny host sum of (k, dim+1)
partials instead of a Spark treeAggregate.

The reference demo ends with a timing comparison against MLlib KMeans;
this one prints its own wall time, which on a CPU host is no device
figure.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import numpy as np

import tensorframes_tpu as tfs
from tensorframes_tpu.models import kmeans


def make_blobs(n, dim, k, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(k, dim) * 10.0
    assign = rng.randint(0, k, n)
    return (centers[assign] + rng.randn(n, dim)).astype(np.float32)


def main(rows: int, dim: int, k: int, iters: int, use_mesh: bool):
    pts = make_blobs(rows, dim, k)
    df = tfs.TensorFrame.from_dict({"features": pts}, num_blocks=8).to_device()

    mesh = None
    if use_mesh:
        from tensorframes_tpu.parallel import data_mesh

        mesh = data_mesh()

    kmeans(df, "features", k, num_iters=1, mesh=mesh)  # warm-up: compile
    t0 = time.perf_counter()
    centers, counts = kmeans(df, "features", k, num_iters=iters, mesh=mesh)
    dt = time.perf_counter() - t0

    print(f"rows={rows} dim={dim} k={k} iters={iters} mesh={use_mesh}")
    print(f"wall={dt:.3f}s  ({rows * iters / dt:,.0f} row-assignments/s)")
    print("cluster sizes:", sorted(int(c) for c in counts))
    assert counts.sum() == rows
    # quality check: mean distance to assigned center must beat random
    d = np.linalg.norm(pts[:, None, :] - centers[None, :, :], axis=-1)
    inertia = d.min(1).mean()
    print(f"mean distance to assigned center: {inertia:.3f}")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=100_000)
    p.add_argument("--dim", type=int, default=100)
    p.add_argument("--k", type=int, default=10)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--mesh", action="store_true")
    a = p.parse_args()
    main(a.rows, a.dim, a.k, a.iters, a.mesh)
