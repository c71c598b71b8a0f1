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

    @staticmethod
    def _two_part(seed, batch=2, heads=4, seq=75, d=16, d2=8, dv=24, k2_heads=1):
        rng = np.random.RandomState(seed)
        arr = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
        return (arr(batch, heads, seq, d), arr(batch, heads, seq, d),
                arr(batch, heads, seq, dv), arr(batch, heads, seq, d2),
                arr(batch, k2_heads, seq, d2))

    @staticmethod
    def _plain(q, k, v, q2, k2, causal):
        """Plain attention on the key written out for every head."""
        heads = q.shape[1]
        qq = jnp.concatenate([q, q2], -1)
        kk = jnp.concatenate([k, jnp.repeat(k2, heads // k2.shape[1], 1)], -1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qq, kk) / np.sqrt(qq.shape[-1])
        if causal:
            n = s.shape[-1]
            s = jnp.where(jnp.arange(n)[:, None] >= jnp.arange(n)[None], s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("k2_heads", [1, 2, 4])
    def test_second_score_part_and_a_value_width_of_its_own(self, causal, k2_heads):
        # latent attention's shapes: a per-head part, a part whose key
        # serves several (here: all, two, one) heads, values wider than
        # either; a sequence that is no multiple of the block
        q, k, v, q2, k2 = self._two_part(5, k2_heads=k2_heads)
        out = flash_attention(q, k, v, q2=q2, k2=k2, causal=causal,
                              block_q=32, block_k=32)
        assert out.shape == v.shape
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(self._plain(q, k, v, q2, k2, causal)),
            rtol=2e-5, atol=2e-6)

    def test_a_narrower_value_alone(self):
        rng = np.random.RandomState(6)
        q, k = (jnp.asarray(rng.randn(100, 16), jnp.float32) for _ in range(2))
        v = jnp.asarray(rng.randn(100, 8), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        ref = full_attention(q, k, v, causal=True)
        assert out.shape == (100, 8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6)

    def test_the_old_call_is_unchanged(self):
        # one width, no second part: the kernel takes three operands as it
        # did, and a second part of zeros adds exactly nothing to it
        q, k, v, q2, k2 = self._two_part(7, dv=16)
        old = flash_attention(q, k, v, causal=True, scale=0.25, block_q=32, block_k=32)
        zero = flash_attention(q, k, v, q2=0 * q2, k2=0 * k2, causal=True, scale=0.25,
                               block_q=32, block_k=32)
        np.testing.assert_array_equal(np.asarray(old), np.asarray(zero))
        one = jax.vmap(jax.vmap(
            lambda a, b, c: full_attention(a, b, c, causal=True, scale=0.25)))(q, k, v)
        np.testing.assert_allclose(np.asarray(old), np.asarray(one), rtol=2e-5, atol=2e-6)
        text = str(jax.make_jaxpr(functools.partial(
            pallas_kernels.flash_attention, causal=True, interpret=True))(q, k, v))
        call = [l for l in text.splitlines() if "pallas_call" in l]
        assert len(call) == 1 and "f32[2,4,75,8]" not in text

    def test_second_part_arguments_are_checked(self):
        q, k, v, q2, k2 = self._two_part(8)
        with pytest.raises(ValueError, match="both"):
            flash_attention(q, k, v, q2=q2)
        with pytest.raises(ValueError, match="second-part key"):
            flash_attention(q, k, v, q2=q2, k2=jnp.concatenate([k2] * 3, 1))

    def test_grad_through_a_second_part_is_the_plain_forms(self):
        q, k, v, q2, k2 = self._two_part(9, batch=1, heads=2, seq=24)
        loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)
        kernel = lambda q, k, v, q2, k2: flash_attention(
            q, k, v, q2=q2, k2=k2, causal=True, block_q=8, block_k=8)
        plain = lambda *a: self._plain(*a, True)
        got = jax.grad(loss(kernel), argnums=(0, 1, 2, 3, 4))(q, k, v, q2, k2)
        want = jax.grad(loss(plain), argnums=(0, 1, 2, 3, 4))(q, k, v, q2, k2)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5)

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
