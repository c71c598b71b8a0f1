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

    @staticmethod
    def _pallas_call(fn, *args):
        """The one `pallas_call` equation in ``fn``'s jaxpr."""
        def calls(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from calls(sub)

        (call,) = calls(jax.make_jaxpr(fn)(*args).jaxpr)
        return call

    @classmethod
    def _grid(cls, fn, *args):
        """The grid of the one `pallas_call` in ``fn``'s jaxpr."""
        return cls._pallas_call(fn, *args).params["grid_mapping"].grid

    @pytest.mark.parametrize("group", [1, 8])
    @pytest.mark.parametrize("blocks", [(32, 32), (24, 40)])
    @pytest.mark.parametrize("window", [1, 7, 64, 100, 150, 1000])
    def test_window_is_a_band_on_a_banded_grid(self, window, blocks, group):
        # query t sees keys t - window < s <= t: against a plain masked
        # softmax (GQA: `group` query heads a key head; 150 positions, so
        # the last blocks are padded); blocks that divide the window and
        # blocks that do not; the grid's key axis spans the band's blocks
        # alone; a window of the whole sequence or more IS the causal kernel
        block_q, block_k = blocks
        rng = np.random.RandomState(window + group)
        seq, kv = 150, 2 if group == 1 else 1
        q = jnp.asarray(rng.randn(1, kv * group, seq, 16), jnp.float32)
        k, v = (jnp.asarray(rng.randn(1, kv, seq, 16), jnp.float32) for _ in range(2))
        call = functools.partial(flash_attention, causal=True, block_q=block_q,
                                 block_k=block_k)
        out = call(q, k, v, window=window)
        t = np.arange(seq)[:, None] - np.arange(seq)[None, :]
        every = lambda a: jnp.repeat(a, group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, every(k), precision="highest") / 4.0
        s = jnp.where(jnp.asarray((t >= 0) & (t < window)), s, -jnp.inf)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), every(v),
                          precision="highest")
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-6)
        nq, nk = -(-seq // block_q), -(-seq // block_k)
        band = max(min((i * block_q + block_q - 1) // block_k, nk - 1)
                   - max(i * block_q - window + 1, 0) // block_k + 1 for i in range(nq))
        grid = self._grid(functools.partial(call, window=window), q, k, v)
        assert grid == (1, kv * group, nq, band)
        if window >= seq:
            np.testing.assert_array_equal(np.asarray(out), np.asarray(call(q, k, v)))
        elif window < 100:
            assert band < nk  # blocks wholly outside the band are never visited

    @classmethod
    def _step_bodies(cls, fn, *args):
        """The two step bodies in the kernel of the one `pallas_call` in
        ``fn``'s jaxpr, (masked, whole): the branches that take the dot
        products, each as the primitive names it holds (with their sub-jaxprs')."""
        def names(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn.primitive.name
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from names(sub)

        kernel = cls._pallas_call(fn, *args).params["jaxpr"]
        bodies = [list(names(branch.jaxpr)) for eqn in kernel.eqns if eqn.primitive.name == "cond"
                  for branch in eqn.params["branches"]]
        bodies = [b for b in bodies if "dot_general" in b]
        assert len(bodies) == 2
        return sorted(bodies, key=lambda b: "iota" not in b)

    def test_without_a_window_the_kernel_traces_as_it_did(self):
        # a whole block's body holds no iota and no select: only a block
        # that needs the positional mask (the diagonal's, a band's edge,
        # the padded tail) builds it, in a causal, a plain and a windowed
        # call; under sparse attention the selection's two selects stay in
        # both bodies
        q = jnp.zeros((1, 8, 150, 16), jnp.float32)
        kv = jnp.zeros((1, 1, 150, 16), jnp.float32)
        sparse = lambda q, k, v: pallas_kernels.sparse_attention(
            q, k, v, jnp.ones((1, 150, 150), jnp.int8), None, q2=q, k2=k, scale=0.25,
            block=32, interpret=True)
        for fn, selects in (
                (functools.partial(flash_attention, causal=True, block_q=32, block_k=32), 0),
                (functools.partial(flash_attention, block_q=32, block_k=32), 0),
                (functools.partial(flash_attention, causal=True, block_q=32, block_k=32,
                                   window=64), 0),
                (sparse, 2)):
            masked, whole = self._step_bodies(fn, q, kv, kv)
            assert "iota" in masked and masked.count("select_n") == 2
            assert "iota" not in whole and whole.count("select_n") == selects
        with pytest.raises(ValueError, match="causal band"):
            flash_attention(q, kv, kv, window=4)
        with pytest.raises(ValueError, match="causal band"):
            flash_attention(q, kv, kv, causal=True, window=0)

    @staticmethod
    def _every_block_masked(monkeypatch):
        """The kernel as it was: every computed block takes the masked body
        (the block classifier answers "edge" for all), in the same order.
        Returns the list of the classifier's calls."""
        calls = []

        def edge(*args, **kwargs):
            calls.append(args)
            return True

        monkeypatch.setattr(pallas_kernels, "_edge", edge)
        return calls

    @pytest.mark.parametrize("causal,window,seq,blocks", [
        (True, None, 128, (32, 32)),   # blocks that divide the sequence
        (True, None, 150, (32, 32)),   # a padded tail
        (True, None, 128, (16, 32)),   # query blocks narrower than key blocks
        (True, None, 150, (24, 40)),
        (True, None, 150, (40, 24)),   # and wider
        (False, None, 128, (32, 32)),  # a plain call
        (False, None, 150, (24, 40)),
        (True, 64, 128, (32, 32)),     # band edges on block boundaries
        (True, 80, 150, (24, 40)),
        (True, 80, 150, (32, 32)),     # band edges inside blocks
        (True, 100, 150, (40, 24)),
    ])
    def test_a_whole_block_runs_unmasked_to_the_same_numbers(
            self, monkeypatch, causal, window, seq, blocks):
        # grouped-query heads (4 over 2) and a second score part (one key
        # head for all): against plain attention, and bit for bit against
        # every block masked in the same block order
        block_q, block_k = blocks
        rng = np.random.RandomState(seq + block_q + (window or 0))
        arr = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
        q, k, v, q2, k2 = (arr(1, 4, seq, 16), arr(1, 2, seq, 16), arr(1, 2, seq, 24),
                           arr(1, 4, seq, 8), arr(1, 1, seq, 8))
        call = lambda: np.asarray(flash_attention(
            q, k, v, q2=q2, k2=k2, causal=causal, block_q=block_q, block_k=block_k,
            window=window))
        out = call()
        t = np.arange(seq)[:, None] - np.arange(seq)[None, :]
        seen = np.ones_like(t, bool) if not causal else (t >= 0) & (t < (window or seq))
        qq = jnp.concatenate([q, q2], -1)
        kk = jnp.concatenate([jnp.repeat(k, 2, 1), jnp.repeat(k2, 4, 1)], -1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qq, kk, precision="highest") / np.sqrt(24)
        s = jnp.where(jnp.asarray(seen), s, -jnp.inf)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), jnp.repeat(v, 2, 1),
                          precision="highest")
        np.testing.assert_allclose(out, np.asarray(want), rtol=2e-5, atol=2e-6)
        if causal:  # the case holds whole blocks, and edge blocks
            assert min(pallas_kernels.block_classes(seq, block_q, block_k, window)) > 0
        classified = self._every_block_masked(monkeypatch)
        np.testing.assert_array_equal(out, call())
        assert classified

    @pytest.mark.parametrize("block", [16, 32])
    def test_a_sparse_whole_block_keeps_the_selection(self, monkeypatch, block):
        # queries 40-63 select nothing in their first key blocks (keys
        # 0-31), so their rows start with nothing seen: against the plain
        # selected softmax, and bit for bit against every block masked
        rng = np.random.RandomState(block)
        f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
        q, k, v, q2, k2 = (f(2, 4, 64, 16), f(2, 4, 64, 16), f(2, 4, 64, 8), f(2, 4, 64, 8),
                           f(2, 1, 64, 8))
        chosen = ((rng.rand(2, 64, 64) < 0.3) | np.eye(64, dtype=bool)) & np.tril(
            np.ones((64, 64), bool))
        chosen[:, 40:, :32] = False
        sink = f(4)
        call = lambda: np.asarray(pallas_kernels.sparse_attention(
            q, k, v, jnp.asarray(chosen, jnp.int8), sink, q2=q2, k2=k2, scale=0.2,
            block=block, interpret=True))
        out = call()
        z = 0.2 * (np.einsum("rhtd,rhsd->rhts", q, k) + np.einsum("rhtd,rsd->rhts", q2, k2[:, 0]))
        z = np.where(chosen[:, None], z, -np.inf)
        top = np.maximum(z.max(-1, keepdims=True), np.asarray(sink)[None, :, None, None])
        e = np.exp(z - top)
        want = np.einsum("rhts,rhsd->rhtd", e / (e.sum(-1, keepdims=True) + np.exp(
            np.asarray(sink)[None, :, None, None] - top)), v)
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
        assert pallas_kernels.block_classes(64, block, block)[0] > 0
        classified = self._every_block_masked(monkeypatch)
        np.testing.assert_array_equal(out, call())
        assert classified

    @pytest.mark.parametrize("window", [None, 1, 24, 64, 100, 1000])
    @pytest.mark.parametrize("seq,blocks", [(128, (32, 32)), (150, (32, 32)),
                                            (150, (24, 40)), (150, (40, 24))])
    def test_block_classes_are_the_positions_and_the_list_is_the_visited_blocks(
            self, seq, blocks, window):
        # each (query block, key block) pair by the positions it holds (the
        # kernel's query rows, padded ones included; keys past the
        # sequence unseen): empty (no key seen), inner (every key seen by
        # every query) or edge; a causal call without a window walks the
        # non-empty pairs, query block by query block, key blocks ascending
        block_q, block_k = blocks
        nq, nk = -(-seq // block_q), -(-seq // block_k)
        t = np.arange(nq * block_q)[:, None]
        s = np.arange(nk * block_k)[None, :]
        seen = (s <= t) & (s > t - (window or nq * block_q)) & (s < seq)
        tiles = seen.reshape(nq, block_q, nk, block_k).transpose(0, 2, 1, 3)
        visited = [(i, j) for i in range(nq) for j in range(nk) if tiles[i, j].any()]
        inner = sum(tiles[i, j].all() for i, j in visited)
        assert pallas_kernels.block_classes(seq, block_q, block_k, window) == (
            inner, len(visited) - inner)
        assert pallas_kernels._visits(seq, block_q, block_k, window) == visited
        if window is None:
            q = jnp.zeros((1, 2, seq, 8), jnp.float32)
            closed = jax.make_jaxpr(functools.partial(
                flash_attention, causal=True, block_q=block_q, block_k=block_k))(q, q, q)
            (table,) = [np.asarray(c) for c in closed.consts if np.ndim(c) == 1]
            np.testing.assert_array_equal(table.reshape(-1, 2), np.asarray(visited))
            assert self._grid(lambda a: flash_attention(
                a, a, a, causal=True, block_q=block_q, block_k=block_k), q) == (
                1, 2, len(visited))

    def test_grad_through_a_window_is_the_plain_forms(self):
        q, k, v = (jnp.asarray(np.random.RandomState(s).randn(1, 2, 24, 8), jnp.float32)
                   for s in (11, 12, 13))
        t = np.arange(24)[:, None] - np.arange(24)[None, :]

        def plain(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(8)
            s = jnp.where(jnp.asarray((t >= 0) & (t < 5)), s, -jnp.inf)
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

        loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)
        kernel = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=8,
                                                 block_k=8, window=5)
        got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
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


class TestHeadLogprob:
    """`head_logprob` (interpreted) against `jax.nn.log_softmax` and
    `take_along_axis` over the whole float32 logits."""

    @staticmethod
    def _plain(x, w, t):
        s = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32), precision="highest")
        return jnp.take_along_axis(jax.nn.log_softmax(s, -1), t[:, None], 1)[:, 0]

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n,vocab,block_t,block_v", [
        (64, 1024, 32, 256),   # both axes whole tiles
        (50, 1000, 16, 256),   # a padded token tail, a masked vocabulary tail
        (48, 320, 16, 256),    # 256 + 64: the last tile a quarter real
        (37, 256, 32, 128),    # tokens padded, the vocabulary whole tiles
        (40, 300, 32, 512),    # one vocabulary tile, wider than V: a block of V
    ])
    def test_matches_log_softmax(self, n, vocab, block_t, block_v, dtype):
        rng = np.random.RandomState(n + vocab)
        d = 32
        # logits spanning about ±80 (x · w has sd 45): the running max moves
        # from tile to tile and an unshifted exponential would overflow
        x = jnp.asarray(8.0 * rng.randn(n, d), dtype)
        w = jnp.asarray(rng.randn(d, vocab), dtype)
        bv = min(block_v, vocab)
        # a tile's first and last column, the next tile's first, V - 1
        ends = [e for e in (0, bv - 1, bv, vocab - 1) if e < vocab]
        t = rng.randint(0, vocab, n)
        t[:len(ends)] = ends
        t = jnp.asarray(t, jnp.int32)
        got = pallas_kernels.head_logprob(x, w, t, block_t=block_t, block_v=block_v,
                                          interpret=True)
        want = self._plain(x, w, t)
        assert got.shape == (n,) and got.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(x.astype(jnp.float32) @ w.astype(jnp.float32)))) > 80
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-5)

    def test_no_logits_leave_the_kernel(self):
        # one pallas_call; no (n, V) float32 array anywhere in the program
        x, w = jnp.zeros((64, 32), jnp.bfloat16), jnp.zeros((32, 1000), jnp.bfloat16)
        text = str(jax.make_jaxpr(functools.partial(
            pallas_kernels.head_logprob, block_t=32, block_v=256, interpret=True))(
                x, w, jnp.zeros((64,), jnp.int32)))
        assert text.count("pallas_call") == 1 and "f32[64,1000]" not in text

    @pytest.mark.parametrize("d,vocab,tiles", [
        (2048, 200192, (1024, 2176)),  # trinity-mini: 200,192 = 92 x 2,176
        (2048, 129280, (1024, 1280)),  # joyai-llm-flash: 101 x 1,280
        (2048, 65536, (1024, 2048)),   # lfm2-8b-a1b: logits of a tile within 9 MiB
        (4096, 32768, (1024, 1024)),   # nemotron's slice: a tile of w within 12 MiB
        (6144, 15104, (1024, 1024)),   # no divisor from 1,024 on: masked
        (64, 256, (1024, 256)),        # a small preset: one vocabulary tile
    ])
    def test_tiles_come_from_the_shape(self, d, vocab, tiles):
        assert pallas_kernels.head_tiles(32768, d, vocab) == tiles
