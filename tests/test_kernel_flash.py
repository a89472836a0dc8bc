"""Flash-attention Pallas kernel vs dense softmax reference (interpret)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import flash_mha


def _ref(q, k, v, scale, causal=True, window=None):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    Sq, Sk = q.shape[1], k.shape[1]
    d = jnp.arange(Sq)[:, None] - jnp.arange(Sk)[None, :]
    ok = d >= 0 if causal else jnp.ones((Sq, Sk), bool)
    if window is not None:
        ok = ok & (d < window)
    s = jnp.where(ok[None, None], s, -2e38)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("S,D,bq,bk", [(128, 64, 64, 64), (192, 32, 64, 64),
                                       (256, 64, 128, 64)])
def test_flash_matches_reference(S, D, bq, bk):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, S, 3, D)), jnp.float32)
               for _ in range(3))
    out = flash_mha(q, k, v, scale=1 / np.sqrt(D), bq=bq, bk=bk,
                    backend="pallas_interpret")
    ref = _ref(q, k, v, 1 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_gqa_and_window():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    out = flash_mha(q, k, v, scale=0.2, window=32, bq=64, bk=64,
                    backend="pallas_interpret")
    kk = jnp.repeat(k, 2, axis=2)
    vv = jnp.repeat(v, 2, axis=2)
    ref = _ref(q, kk, vv, 0.2, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_bf16_padding():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 100, 2, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 100, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 100, 2, 64)), jnp.bfloat16)
    out = flash_mha(q, k, v, scale=0.125, bq=64, bk=64,
                    backend="pallas_interpret")
    ref = _ref(q.astype(jnp.float32), k.astype(jnp.float32),
               v.astype(jnp.float32), 0.125)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)


def test_flash_model_path_matches_default():
    """cfg.flash_attention routes training attention through the Pallas
    kernel (here in interpret mode) with identical outputs."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import init_model, apply_model
    cfg = get_config("qwen1.5-0.5b").reduced()
    cfg = dataclasses.replace(cfg, n_layers=1, attn_chunk=32)
    params = init_model(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 64)), jnp.int32)
    base, _, _ = apply_model(params, cfg, toks)
    cfg_f = dataclasses.replace(cfg, flash_attention="pallas_interpret")
    flash, _, _ = apply_model(params, cfg_f, toks)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(base),
                               rtol=2e-4, atol=2e-4)
