"""Inputs from the seed, the same on the device and in numpy.

Every value is a hash of its flat index and the seed in 32-bit unsigned
arithmetic, which `jax.numpy` and numpy compute alike. The device makes
the whole column in one jitted call; the reference makes any rows it
wants again on the host and needs no copy of the input.
"""

import numpy as np

_M32 = 0xFFFFFFFF
_C1, _C2 = np.uint32(0x7FEB352D), np.uint32(0x846CA68B)
_S15, _S16 = np.uint32(15), np.uint32(16)


def seed_word(seed: int) -> np.uint32:
    """Fold any whole number into 32 bits (seeds pass 2**31)."""
    s, w = abs(int(seed)), 0x9E3779B9
    while True:
        w = ((w ^ (s & _M32)) * 0x85EBCA6B + 0xC2B2AE35) & _M32
        s >>= 32
        if s == 0:
            return np.uint32(w)


def mix32(x, key):
    """A 32-bit finalizer over a uint32 array, numpy or jax."""
    x = x ^ key
    x = x ^ (x >> _S16)
    x = x * _C1
    x = x ^ (x >> _S15)
    x = x * _C2
    x = x ^ (x >> _S16)
    return x


def small_ints(idx, key, below: int = 1024):
    """Integers in [0, below), `below` a power of two, as float32."""
    return (mix32(idx, key) & np.uint32(below - 1)).astype(np.float32)


def unit_fractions(idx, key):
    """k/256 for k in [0, 256): exact in float32 (and in bfloat16)."""
    return (mix32(idx, key) >> np.uint32(24)).astype(np.float32) * np.float32(
        1.0 / 256.0
    )


KINDS = {"small_ints": small_ints, "unit_fractions": unit_fractions}


def on_device(jax, kind: str, shape, seed: int):
    """The whole array in one jitted call; the seed is an argument, so
    every seed runs the same program."""
    import jax.numpy as jnp

    n = int(np.prod(shape))
    if n >= 2**32:
        raise ValueError(f"{n} values do not fit a 32-bit index")
    fn = KINDS[kind]

    @jax.jit
    def make(key):
        return fn(jnp.arange(n, dtype=jnp.uint32), key).reshape(shape)

    return make(jnp.asarray(seed_word(seed), dtype=jnp.uint32))


def rows_on_host(kind: str, rows, width: int, seed: int) -> np.ndarray:
    """Rows `rows` (any index array) of the (n, width) array that
    `on_device` makes; width 1 gives a flat column."""
    rows = np.asarray(rows, dtype=np.uint32)
    if width == 1:
        idx = rows
    else:
        idx = rows[:, None] * np.uint32(width) + np.arange(
            width, dtype=np.uint32
        )
    return KINDS[kind](idx, seed_word(seed))


def block_offsets(rows: int, blocks: int):
    """Block boundaries as `TensorFrame.repartition` cuts them."""
    return [int(v) for v in np.linspace(0, rows, blocks + 1).astype(int)]
