"""Pallas flash-attention kernel: parity with reference attention.

Runs in interpret mode on the CPU suite (passed explicitly); the same
kernel is compiled for a described v5e in `tests/test_tpu_compile.py`
and run on the chip by `chip_smoke.py`."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import functools

from tensorframes_tpu.ops import pallas_kernels
from tensorframes_tpu.parallel.ring import full_attention

# the kernel never picks interpret mode by itself: CPU tests say so
flash_attention = functools.partial(
    pallas_kernels.flash_attention, interpret=True
)


def _qkv(seq, d, seed=0):
    rng = np.random.RandomState(seed)
    return (
        jnp.asarray(rng.randn(seq, d), jnp.float32),
        jnp.asarray(rng.randn(seq, d), jnp.float32),
        jnp.asarray(rng.randn(seq, d), jnp.float32),
    )


class TestFlashAttention:
    @pytest.mark.parametrize("seq,d", [(64, 16), (128, 8), (256, 32)])
    def test_matches_full(self, seq, d):
        q, k, v = _qkv(seq, d)
        out = flash_attention(q, k, v, block_q=64, block_k=64)
        ref = full_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
        )

    def test_causal(self):
        q, k, v = _qkv(128, 16, seed=1)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        ref = full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
        )

    def test_unpadded_tail(self):
        # seq not a multiple of the block: padded keys must not leak in
        q, k, v = _qkv(100, 8, seed=2)
        out = flash_attention(q, k, v, block_q=64, block_k=64)
        ref = full_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
        )

    def test_causal_tail(self):
        q, k, v = _qkv(75, 8, seed=3)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        ref = full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
        )

    def test_grad_is_full_attentions(self):
        # the kernel has no transpose rule of its own: its custom_vjp
        # backward is full_attention's, also under the per-head vmap
        # TransformerLM uses
        rng = np.random.RandomState(4)
        q, k, v = (
            jnp.asarray(rng.randn(2, 24, 8), jnp.float32) for _ in range(3)
        )

        def loss(attn):
            return lambda q, k, v: jnp.sum(
                jax.vmap(lambda a, b, c: attn(a, b, c, causal=True))(q, k, v)
                ** 2
            )

        got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(loss(full_attention), argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=2e-4, atol=2e-5
            )

    def test_transformer_tpu_branch_trains(self, monkeypatch):
        # TransformerLM picks the kernel when the backend is a TPU — a
        # branch no CPU run enters by itself; steer it here (interpreted)
        # and take training steps through the kernel's custom_vjp
        from tensorframes_tpu.models import TransformerLM

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(pallas_kernels, "flash_attention", flash_attention)
        lm = TransformerLM(vocab=32, d_model=16, n_heads=2, n_layers=1, max_seq=16)
        tokens = jnp.asarray(np.random.RandomState(5).randint(0, 32, 16))
        params, first = lm.train_step(lm.params, tokens)
        for _ in range(3):
            params, loss = lm.train_step(params, tokens)
        assert np.isfinite(float(loss)) and float(loss) < float(first)
