"""MoE dispatch correctness + MLA decode paths."""
import dataclasses
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.nn.moe import moe_init, moe_apply, route, dispatch_compute
from repro.nn.mla import mla_init, mla_apply, init_mla_cache
from repro.nn.common import act_fn


def _moe_cfg():
    return get_config("qwen3-moe-235b-a22b").reduced()


def _dense_moe(x, idx, w, p, act):
    """Every token through each of its routed experts, in numpy."""
    wi, wg, wo = (np.asarray(p[k]) for k in
                  ("experts_wi", "experts_wg", "experts_wo"))
    xn = np.asarray(x)
    ref = np.zeros(xn.shape, np.float32)
    for t in range(xn.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j])
            h = xn[t] @ wi[e]
            g = np.asarray(act_fn(act)(jnp.asarray(xn[t] @ wg[e])))
            ref[t] += float(w[t, j]) * ((g * h) @ wo[e])
    return ref


def test_dispatch_matches_dense_reference():
    cfg = _moe_cfg()
    key = jax.random.PRNGKey(0)
    p = moe_init(key, cfg)
    rng = np.random.default_rng(0)
    T, d = 24, cfg.d_model
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    idx, w, aux = route(p, x, cfg)
    got, stats = dispatch_compute(x, idx, w, p["experts_wi"],
                                  p["experts_wg"], p["experts_wo"],
                                  act=cfg.act)
    ref = _dense_moe(x, idx, w, p, cfg.act)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-3, atol=2e-3)
    used = len(np.unique(np.asarray(idx)))
    assert np.asarray(stats).tolist() == [T * cfg.top_k, used]


def test_no_drop_routing_under_a_skewed_router():
    """Every token sends its first choice to expert 3: the expert gets all
    T of them (a capacity of 1.25·T·k/E would drop most) and each is
    computed exactly as the dense reference computes it."""
    cfg = _moe_cfg()
    p = moe_init(jax.random.PRNGKey(1), cfg)
    T = 32
    x = jnp.asarray(np.random.default_rng(1).normal(size=(T, cfg.d_model)),
                    jnp.float32)
    idx, w, _ = route(p, x, cfg)
    idx = np.asarray(idx).copy()
    for t in range(T):                    # 3 first, the rest distinct
        rest = [e for e in idx[t] if e != 3][:cfg.top_k - 1]
        idx[t] = [3] + rest
    idx = jnp.asarray(idx)
    out, stats = dispatch_compute(x, idx, w, p["experts_wi"],
                                  p["experts_wg"], p["experts_wo"],
                                  act=cfg.act)
    ref = _dense_moe(x, idx, w, p, cfg.act)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)
    assert int(stats[0]) == T * cfg.top_k
    again, _ = dispatch_compute(x, idx, w, p["experts_wi"], p["experts_wg"],
                                p["experts_wo"], act=cfg.act)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of the 8 experts (2 each, first expert 0, 2, 4, 6), as
    four chips of an expert-parallel deployment hold them: their parts of
    the output and their counts add up to the layer that holds all 8."""
    cfg = _moe_cfg()
    assert cfg.n_experts == 8
    p = moe_init(jax.random.PRNGKey(4), cfg)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(20, cfg.d_model)),
                    jnp.float32)
    idx, w, _ = route(p, x, cfg)
    whole, whole_stats = dispatch_compute(
        x, idx, w, p["experts_wi"], p["experts_wg"], p["experts_wo"],
        act=cfg.act)
    parts = [dispatch_compute(x, idx, w, p["experts_wi"][o:o + 2],
                              p["experts_wg"][o:o + 2],
                              p["experts_wo"][o:o + 2], act=cfg.act,
                              offset=o) for o in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(np.asarray(o) for o, _ in parts),
                               np.asarray(whole), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(sum(np.asarray(s) for _, s in parts),
                                  np.asarray(whole_stats))


def test_tpu_grouped_matmul_matches_ragged_dot(monkeypatch):
    """The TPU lowering (rows padded to the kernel's block, the Pallas
    kernel in interpret mode here) against ``lax.ragged_dot``; rows past
    the groups are not compared (left undefined)."""
    from repro.nn import moe
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    monkeypatch.setattr(moe, "moe_experts", lambda x, w, g, *, tiling: gmm(
        x, w, g, tiling=tiling, interpret=True))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(40, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 256, 384)), jnp.float32)
    sizes = jnp.asarray([9, 0, 20, 7], jnp.int32)
    got = moe._grouped_tpu(x, w, sizes)
    want = jax.lax.ragged_dot(x, w, sizes,
                              precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(got)[:36], np.asarray(want)[:36],
                               rtol=1e-4, atol=1e-3)


def test_router_weights_normalized():
    cfg = _moe_cfg()
    p = moe_init(jax.random.PRNGKey(2), cfg)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(8, cfg.d_model)),
                    jnp.float32)
    _, w, _ = route(p, x, cfg)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)


def test_sigmoid_router_dsv3():
    cfg = get_config("deepseek-v3-671b").reduced()
    p = moe_init(jax.random.PRNGKey(3), cfg)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(8, cfg.d_model)),
                    jnp.float32)
    idx, w, aux = route(p, x, cfg)
    assert float(aux) == 0.0                      # aux-free scheme
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)


def test_mla_absorb_equals_naive_decode():
    cfg = get_config("deepseek-v3-671b").reduced()
    key = jax.random.PRNGKey(0)
    p = mla_init(key, cfg)
    B, S = 2, 6
    x = jnp.asarray(np.random.default_rng(5).normal(size=(B, S, cfg.d_model)),
                    jnp.float32)
    for absorb in (False, True):
        c = init_mla_cache(cfg, B, 16, 1)
        cache = {"ckv": c["ckv"][0], "kr": c["kr"][0], "pos": c["pos"]}
        cfg_i = dataclasses.replace(cfg, mla_absorb=absorb)
        outs = []
        cur = cache
        for t in range(S):
            o, cur = mla_apply(p, x[:, t:t + 1], cfg_i, cache=cur)
            outs.append(o)
        if absorb:
            out_a = jnp.concatenate(outs, 1)
        else:
            out_n = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_n),
                               rtol=2e-4, atol=2e-4)


def test_mla_decode_matches_prefill():
    cfg = get_config("deepseek-v3-671b").reduced()
    p = mla_init(jax.random.PRNGKey(1), cfg)
    B, S = 1, 8
    x = jnp.asarray(np.random.default_rng(6).normal(size=(B, S, cfg.d_model)),
                    jnp.float32)
    full, _ = mla_apply(p, x, cfg)                  # parallel (no cache)
    c = init_mla_cache(cfg, B, 16, 1)
    cur = {"ckv": c["ckv"][0], "kr": c["kr"][0], "pos": c["pos"]}
    outs = []
    for t in range(S):
        o, cur = mla_apply(p, x[:, t:t + 1], cfg, cache=cur)
        outs.append(o)
    step = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               rtol=3e-3, atol=3e-3)
