"""`models.lm`'s head: in the weights' dtype one fused kernel
(`ops.pallas_kernels.head_logprob`, interpreted here), under
``enable_lm_head_fp32`` the float32 chunked head it was. At each scoring
configuration's small preset the kernel's log-probabilities equal the
chunked XLA head's (a copy of it is kept below as the oracle), the float32
head of `hy4-preview` lowers to the text it lowered to before the kernel
came, and `lm.score` books the kernel's tokens."""

import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import tensorframes_tpu as tfs
from tensorframes_tpu.models import lm, moe
from tensorframes_tpu.utils import telemetry as tele

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNEL = ["lfm2-8b-a1b", "joyai-llm-flash", "nemotron-3-super-120b-a12b", "trinity-mini"]


def _small(name):
    """(the configuration as its runner runs it at the small preset, held)."""
    from perf.runners import map_blocks_lm, map_blocks_lm_hybrid, map_blocks_lm_latent

    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        config = json.load(f)
    if name == "joyai-llm-flash":
        return map_blocks_lm_latent.model_config(config, True), None
    if name in ("lfm2-8b-a1b", "trinity-mini"):
        return map_blocks_lm.model_config(config, True), None
    return map_blocks_lm_hybrid.model_config(config, True)


def _chunked_head(config, params, h, tokens, interpret=None):
    """The head before the kernel: the float32 logits of 2,048 tokens at a
    time in HBM, their log-sum-exp, the target's logit by a flat gather."""
    with jax.named_scope("lm.head"):
        rows, seq, d = h.shape
        x = lm._rms_norm(h, params["final_norm"], float(config["norm_eps"]))
        target = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        n = rows * seq
        chunk = min(2048, n)
        pad = (-n) % chunk
        x = jnp.pad(x.reshape(n, d), ((0, pad), (0, 0)))
        t = jnp.pad(target.reshape(n), (0, pad))

        def one(args):
            xc, tc = args
            if config.get("enable_lm_head_fp32"):
                logits = jnp.dot(xc, params["head"].astype(jnp.float32),
                                 precision=lax.Precision.HIGHEST)
            else:
                logits = lm._matmul(xc, params["head"])
            lse = jax.nn.logsumexp(logits, axis=-1)
            return moe._along_rows(logits, tc[:, None])[:, 0] - lse

        lp = lax.map(one, (x.reshape(-1, chunk, d), t.reshape(-1, chunk)))
        return lp.reshape(-1)[:n].reshape(rows, seq).at[:, -1].set(0.0)


def _tokens(cfg, rows=2, seq=64, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, int(cfg["vocab_size"]), size=(rows, seq)), jnp.int32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", KERNEL)
def test_the_kernel_head_equals_the_chunked_head(name, dtype, monkeypatch):
    cfg, held = _small(name)
    cfg = dict(cfg, dtype=dtype)
    params = lm.init_params(cfg, 1, held)
    tokens = _tokens(cfg)
    fn = jax.jit(lm.scoring_fn(cfg, held=held, interpret=True))
    got = np.asarray(fn(tokens, params)["token_logprob"])
    monkeypatch.setattr(lm, "_head", _chunked_head)
    want = np.asarray(jax.jit(lm.scoring_fn(cfg, held=held, interpret=True))(
        tokens, params)["token_logprob"])
    assert np.all(got[:, -1] == 0) and np.all(got[:, :-1] < 0)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_the_float32_head_lowers_as_it_did():
    """`hy4-preview` states a float32 head: its program at the small
    preset lowers to the StableHLO it lowered to before the kernel came
    (sha-256 of the text, which holds no source locations), but for the
    attention kernel's two step bodies and its list of visited blocks
    (with the kernels as they were, the same program hashed to
    5a0ccb5cb8e615e7...)."""
    cfg, held = _small("hy4-preview")
    assert cfg["enable_lm_head_fp32"]
    params = jax.eval_shape(lambda: lm.init_params(cfg, 0, held))
    fn = lm.scoring_fn(cfg, held=held, interpret=True)
    text = jax.jit(fn).lower(jnp.zeros((2, 64), jnp.int32), params).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "3e7a4a0f385cf01226cd9a895899d6e487140ab4fd700930139ca03c388a5928")


@pytest.mark.parametrize("name", KERNEL + ["hy4-preview"])
def test_the_head_kernels_tokens_are_counted(name):
    cfg, held = _small(name)
    params = lm.init_params(cfg, 0, held)
    toks = np.asarray(_tokens(cfg, rows=2, seq=64))
    frame = tfs.TensorFrame([tfs.Column("tokens", jnp.asarray(toks))], [0, 1, 2])
    before = dict(tele.flat_counters())
    lm.score(lm.scoring_fn(cfg, held=held, interpret=True), frame, params, cfg)
    c = {k: v - before.get(k, 0) for k, v in tele.flat_counters().items()}
    assert c["lm.tokens"] == 2 * 64
    assert c["lm.head_kernel_tokens"] == (0 if name == "hy4-preview" else 2 * 64)
    # `tfs.diagnostics()`' "model" lines list it beside every lm.* counter
    counters = tele.flat_counters()
    assert tfs.diagnostics(format="json")["model"]["lm.head_kernel_tokens"] == (
        counters["lm.head_kernel_tokens"])
