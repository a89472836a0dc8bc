"""Attention: chunked==dense, sliding window, softcap, GQA padding
equivalence, causality property (hypothesis)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:                      # property tests are optional (extras: [test])
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.nn.attention import mha, kv_of_q_map


def _qkv(seed, B=2, S=32, Hq=4, Hkv=2, D=16):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    return q, k, v


def test_chunked_equals_dense():
    q, k, v = _qkv(0)
    kvm = kv_of_q_map(4, 2, 4, 2)
    pos = jnp.arange(32)
    a = mha(q, k, v, kvm, scale=0.25, q_pos=pos, k_pos=pos, chunk=8)
    b = mha(q, k, v, kvm, scale=0.25, q_pos=pos, k_pos=pos, chunk=0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    c = mha(q, k, v, kvm, scale=0.25, q_pos=pos, k_pos=pos, chunk=8,
            unroll=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                               rtol=1e-5, atol=1e-5)


def test_sliding_window_masks_past():
    q, k, v = _qkv(1)
    kvm = kv_of_q_map(4, 2, 4, 2)
    pos = jnp.arange(32)
    w = mha(q, k, v, kvm, scale=0.25, q_pos=pos, k_pos=pos, window=4)
    # perturb tokens far outside the window of the last query: no effect
    k2 = k.at[:, :8].set(jnp.asarray(
        np.random.default_rng(9).normal(size=k[:, :8].shape), jnp.float32))
    w2 = mha(q, k2, v, kvm, scale=0.25, q_pos=pos, k_pos=pos, window=4)
    np.testing.assert_allclose(np.asarray(w[:, -1]), np.asarray(w2[:, -1]),
                               rtol=1e-5, atol=1e-6)


def test_softcap_bounds_logit_effect():
    q, k, v = _qkv(2)
    kvm = kv_of_q_map(4, 2, 4, 2)
    pos = jnp.arange(32)
    a = mha(q * 100.0, k, v, kvm, scale=1.0, q_pos=pos, k_pos=pos, cap=5.0)
    assert np.all(np.isfinite(np.asarray(a)))


def test_head_padding_equivalence():
    """Padded-head attention (zeroed padded wo rows) == unpadded module."""
    import dataclasses
    from repro.configs import get_config
    from repro.nn.attention import attn_init, attn_apply
    cfg = get_config("qwen1.5-0.5b").reduced()
    cfg = dataclasses.replace(cfg, n_heads=3, n_kv_heads=3, qkv_bias=False)
    cfgp = dataclasses.replace(cfg, pad_heads_to=4)
    key = jax.random.PRNGKey(0)
    p = attn_init(key, cfg)
    pp = attn_init(key, cfgp)
    hd = cfg.head_dim_r
    # copy logical weights into the padded module
    for nm in ("wq", "wk", "wv"):
        w = np.zeros(pp[nm].shape, np.float32)
        w[:, :cfg.n_heads * hd] = np.asarray(p[nm])
        pp[nm] = jnp.asarray(w)
    wo = np.zeros(pp["wo"].shape, np.float32)
    wo[:cfg.n_heads * hd] = np.asarray(p["wo"])
    pp["wo"] = jnp.asarray(wo)

    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 16, 128)),
                    jnp.float32)
    a, _ = attn_apply(p, x, cfg)
    b, _ = attn_apply(pp, x, cfgp)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-4)


def _check_causality(seed, pert_pos):
    """Output at position i is independent of tokens at positions > i."""
    q, k, v = _qkv(seed % 100, S=8)
    kvm = kv_of_q_map(4, 2, 4, 2)
    pos = jnp.arange(8)
    base = mha(q, k, v, kvm, scale=0.25, q_pos=pos, k_pos=pos)
    cut = 8 - pert_pos
    rng = np.random.default_rng(seed)
    k2 = k.at[:, cut:].add(jnp.asarray(rng.normal(size=k[:, cut:].shape),
                                       jnp.float32))
    v2 = v.at[:, cut:].add(1.0)
    out = mha(q, k2, v2, kvm, scale=0.25, q_pos=pos, k_pos=pos)
    np.testing.assert_allclose(np.asarray(out[:, :cut]),
                               np.asarray(base[:, :cut]),
                               rtol=1e-5, atol=1e-5)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10_000), st.integers(1, 3))
    def test_causality_property(seed, pert_pos):
        _check_causality(seed, pert_pos)
else:
    @pytest.mark.parametrize("seed,pert_pos",
                             [(0, 1), (7, 2), (123, 3), (4242, 1)])
    def test_causality_property(seed, pert_pos):
        _check_causality(seed, pert_pos)


def test_yarn_on_full_layers_only():
    """With yarn configured, a full layer's rotary (no window, or the
    scanned "global" window) takes yarn's frequencies and scales cos and
    sin by the attention factor, so q·k at position 0 grows by its
    square; a window layer keeps plain ``rope_theta``."""
    from repro.configs.base import ModelConfig
    from repro.nn.blocks import GLOBAL_WINDOW
    from repro.nn.common import apply_rope, layer_rope, rope_freqs, \
        yarn_inv_freq
    cfg = ModelConfig(head_dim=16, n_heads=2, d_model=32, rope_theta=1e4,
                      rope_yarn_factor=8.0, rope_yarn_original_max=64)
    att = 0.1 * np.log(8.0) + 1.0
    for full in (True, jnp.asarray(GLOBAL_WINDOW) >= GLOBAL_WINDOW):
        inv, scale = layer_rope(cfg, full)
        np.testing.assert_array_equal(
            np.asarray(inv), yarn_inv_freq(16, 1e4, 8.0, 64))
        assert float(scale) == pytest.approx(att)
    inv, scale = layer_rope(cfg, jnp.asarray(24) >= GLOBAL_WINDOW)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(rope_freqs(16,
                                                                      1e4)),
                               rtol=1e-6)
    assert float(scale) == 1.0
    q, k, _ = _qkv(7, B=1, S=4, Hq=1, Hkv=1, D=16)
    pos = jnp.arange(4)
    inv, scale = layer_rope(cfg, True)
    qr = apply_rope(q, pos, inv_freq=inv, scale=scale)
    kr = apply_rope(k, pos, inv_freq=inv, scale=scale)
    np.testing.assert_allclose(np.asarray((qr * kr).sum(-1))[0, 0],
                               att ** 2 * np.asarray((q * k).sum(-1))[0, 0],
                               rtol=1e-5, atol=1e-5)
