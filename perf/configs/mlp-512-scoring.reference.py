"""Plain reference of `mlp-512-scoring`: a float64 numpy forward pass
(chip_smoke.py's `_numpy_mlp`), with the weights made here from the seed.
Nothing of the package is imported."""

import numpy as np


def make_params(sizes, seed):
    """[(w, b)] float32: He-normal weights, biases N(0, 0.1)."""
    rng = np.random.RandomState(int(seed) % (2**32 - 1))
    params = []
    for fan_in, fan_out in zip(sizes[:-1], sizes[1:]):
        w = (rng.standard_normal((fan_in, fan_out)) * np.sqrt(2.0 / fan_in))
        b = rng.standard_normal((fan_out,)) * 0.1
        params.append((w.astype(np.float32), b.astype(np.float32)))
    return params


def forward(x, params, dtype=np.float64):
    """relu hidden layers, softmax out; rows of probabilities."""
    h = np.asarray(x).astype(dtype)
    for i, (w, b) in enumerate(params):
        h = h @ np.asarray(w).astype(dtype) + np.asarray(b).astype(dtype)
        if i < len(params) - 1:
            h = np.maximum(h, 0)
    e = np.exp(h - h.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def _two_bf16(a):
    """float32 `a` as two bfloat16 pieces (held in float32): a ~ hi + lo."""
    from ml_dtypes import bfloat16

    a = np.asarray(a, np.float32)
    hi = a.astype(bfloat16).astype(np.float32)
    lo = (a - hi).astype(bfloat16).astype(np.float32)
    return hi, lo


def forward_three_pass(x, params):
    """The control: the same forward pass with each matmul in three
    bfloat16 passes (hi·hi + hi·lo + lo·hi, float32 accumulation), what
    the chip does at precision `high`, one step below `highest`."""
    h = np.asarray(x, np.float32)
    for i, (w, b) in enumerate(params):
        hh, hl = _two_bf16(h)
        wh, wl = _two_bf16(w)
        h = (hh @ wh + hh @ wl + hl @ wh).astype(np.float32) + np.asarray(b, np.float32)
        if i < len(params) - 1:
            h = np.maximum(h, 0)
    e = np.exp(h - h.max(axis=1, keepdims=True))
    return (e / e.sum(axis=1, keepdims=True)).astype(np.float32)
