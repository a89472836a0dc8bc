"""Transformer-family blocks: dense, MoE, hybrid (attn∥SSM), xLSTM, enc/dec.

Every block is (init, apply) with apply(params, x, cfg, *, window, cache,
positions) → (x_out, new_cache, aux).  ``window`` is a traced per-layer
scalar: −1 ⇒ global attention (implemented branchlessly as a huge window),
so alternating local/global stacks scan over a single homogeneous body.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .common import (linear, linear_init, mlp_init, mlp_apply, norm_init,
                     norm_apply)
from .attention import attn_init, attn_apply
from .mla import mla_init, mla_apply
from .moe import moe_init, moe_apply
from .ssm import ssm_init, ssm_apply
from .xlstm import (mlstm_init, mlstm_apply, slstm_init, slstm_apply)

GLOBAL_WINDOW = np.int32(2 ** 30)   # "-1 == global" sentinel resolves to this
# a layer's aux, summed over layers: (load-balance loss, routed (token,
# expert) assignments, experts that got one); only expert layers fill it
AUX_SIZE = 3


def _win(window):
    """Traced per-layer window: negative ⇒ effectively global."""
    if window is None:
        return None
    return jnp.where(window < 0, GLOBAL_WINDOW, window)


# --- dense / moe decoder block ----------------------------------------------

def decoder_block_init(key, cfg, ffn: str = "dense") -> dict:
    ks = jax.random.split(key, 4)
    p = {}
    if cfg.use_mla:
        p["mla"] = mla_init(ks[0], cfg)
    else:
        p["attn"] = attn_init(ks[0], cfg)
    if ffn == "moe":
        p["moe"] = moe_init(ks[1], cfg)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mac,
                            cfg.gated_mlp, cfg.mlp_bias, cfg.pdtype)
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ln1"))
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ln2"))
    if cfg.post_norm:
        p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ln1p"))
        p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ln2p"))
    return p


def decoder_block_apply(p, x, cfg, *, ffn: str = "dense", window=None,
                        cache=None, positions=None, layer=None):
    """``layer``: the layer's index into whole expert stacks in
    ``p['moe']`` (None: they are this layer's own, see ``moe_apply``)."""
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "ln1")
    if cfg.use_mla:
        a, new_cache = mla_apply(p["mla"], h, cfg, cache=cache,
                                 positions=positions)
    else:
        a, new_cache = attn_apply(p["attn"], h, cfg, layer_window=_win(window),
                                  cache=cache, positions=positions)
    if cfg.post_norm:
        a = norm_apply(p, a, cfg.norm, cfg.norm_eps, "ln1p")
    x = x + a
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "ln2")
    aux = jnp.zeros((AUX_SIZE,), jnp.float32)
    if ffn == "moe":
        f, loss, stats = moe_apply(p["moe"], h, cfg, layer=layer)
        aux = jnp.concatenate([loss[None], stats])
    else:
        f = mlp_apply(p["mlp"], h, cfg.mac, cfg.act, cfg.gated_mlp,
                      cfg.cdtype)
    if cfg.post_norm:
        f = norm_apply(p, f, cfg.norm, cfg.norm_eps, "ln2p")
    return x + f, new_cache, aux


# --- hybrid block (Hymba: parallel attention + SSM heads) --------------------

def hybrid_block_init(key, cfg) -> dict:
    ks = jax.random.split(key, 5)
    p = {"attn": attn_init(ks[0], cfg), "ssm": ssm_init(ks[1], cfg)}
    p["mlp"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.mac,
                        cfg.gated_mlp, cfg.mlp_bias, cfg.pdtype)
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ln1"))
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ln2"))
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "na"))
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ns"))
    return p


def hybrid_block_apply(p, x, cfg, *, window=None, cache=None, positions=None):
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "ln1")
    ac, sc = (None, None) if cache is None else (cache["attn"], cache["ssm"])
    a, ac2 = attn_apply(p["attn"], h, cfg, layer_window=_win(window),
                        cache=ac, positions=positions)
    s, sc2 = ssm_apply(p["ssm"], h, cfg, cache=sc)
    mix = 0.5 * (norm_apply(p, a, cfg.norm, cfg.norm_eps, "na")
                 + norm_apply(p, s, cfg.norm, cfg.norm_eps, "ns"))
    x = x + mix
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "ln2")
    f = mlp_apply(p["mlp"], h, cfg.mac, cfg.act, cfg.gated_mlp, cfg.cdtype)
    new_cache = None if cache is None else {"attn": ac2, "ssm": sc2}
    return x + f, new_cache, jnp.zeros((), jnp.float32)


# --- xLSTM blocks -------------------------------------------------------------

def mlstm_block_init(key, cfg) -> dict:
    p = {"mlstm": mlstm_init(key, cfg)}
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ln1"))
    return p


def mlstm_block_apply(p, x, cfg, *, cache=None):
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "ln1")
    o, new_cache = mlstm_apply(p["mlstm"], h, cfg, cache=cache)
    return x + o, new_cache, jnp.zeros((), jnp.float32)


def slstm_block_init(key, cfg) -> dict:
    p = {"slstm": slstm_init(key, cfg)}
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ln1"))
    return p


def slstm_block_apply(p, x, cfg, *, cache=None):
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "ln1")
    o, new_cache = slstm_apply(p["slstm"], h, cfg, cache=cache)
    return x + o, new_cache, jnp.zeros((), jnp.float32)


# --- encoder block / cross-attention decoder block (whisper) ----------------

def encoder_block_init(key, cfg) -> dict:
    ks = jax.random.split(key, 2)
    p = {"attn": attn_init(ks[0], cfg)}
    p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mac,
                        cfg.gated_mlp, cfg.mlp_bias, cfg.pdtype)
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ln1"))
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "ln2"))
    return p


def encoder_block_apply(p, x, cfg):
    """Bidirectional self-attention block (no mask, no cache)."""
    from .attention import mha, kv_of_q_map
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "ln1")
    B, S, _ = h.shape
    hd = cfg.head_dim_r
    q = linear(p["attn"], "wq", h, cfg.mac, cfg.cdtype).reshape(
        B, S, cfg.n_heads_p, hd)
    k = linear(p["attn"], "wk", h, cfg.mac, cfg.cdtype).reshape(
        B, S, cfg.n_kv_p, hd)
    v = linear(p["attn"], "wv", h, cfg.mac, cfg.cdtype).reshape(
        B, S, cfg.n_kv_p, hd)
    pos = jnp.arange(S)
    kvm = kv_of_q_map(cfg.n_heads, cfg.n_kv_heads, cfg.n_heads_p, cfg.n_kv_p)
    o = mha(q, k, v, kvm, scale=1.0 / np.sqrt(hd), q_pos=pos, k_pos=pos,
            causal=False, chunk=cfg.attn_chunk, unroll=cfg.unroll_scans)
    o = linear(p["attn"], "wo", o.reshape(B, S, -1), cfg.mac, cfg.cdtype)
    x = x + o
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "ln2")
    return x + mlp_apply(p["mlp"], h, cfg.mac, cfg.act, cfg.gated_mlp,
                         cfg.cdtype)


def xattn_decoder_block_init(key, cfg) -> dict:
    ks = jax.random.split(key, 3)
    p = {"attn": attn_init(ks[0], cfg), "xattn": attn_init(ks[1], cfg)}
    p["mlp"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.mac,
                        cfg.gated_mlp, cfg.mlp_bias, cfg.pdtype)
    for nm in ("ln1", "lnx", "ln2"):
        p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, nm))
    return p


def xattn_decoder_block_apply(p, x, enc_kv, cfg, *, cache=None,
                              positions=None):
    """Causal self-attn + cross-attn to precomputed encoder k/v."""
    from .attention import mha, kv_of_q_map
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "ln1")
    sc = None if cache is None else cache["self"]
    a, sc2 = attn_apply(p["attn"], h, cfg, cache=sc, positions=positions)
    x = x + a
    # cross-attention
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "lnx")
    B, S, _ = h.shape
    hd = cfg.head_dim_r
    q = linear(p["xattn"], "wq", h, cfg.mac, cfg.cdtype).reshape(
        B, S, cfg.n_heads_p, hd)
    ek, ev = enc_kv
    Se = ek.shape[1]
    kvm = kv_of_q_map(cfg.n_heads, cfg.n_kv_heads, cfg.n_heads_p, cfg.n_kv_p)
    o = mha(q, ek, ev, kvm, scale=1.0 / np.sqrt(hd),
            q_pos=jnp.zeros((S,), jnp.int32),
            k_pos=jnp.zeros((Se,), jnp.int32), causal=False,
            chunk=cfg.attn_chunk, unroll=cfg.unroll_scans)
    x = x + linear(p["xattn"], "wo", o.reshape(B, S, -1), cfg.mac, cfg.cdtype)
    h = norm_apply(p, x, cfg.norm, cfg.norm_eps, "ln2")
    x = x + mlp_apply(p["mlp"], h, cfg.mac, cfg.act, cfg.gated_mlp,
                      cfg.cdtype)
    new_cache = None if cache is None else {"self": sc2}
    return x, new_cache, jnp.zeros((), jnp.float32)


def cross_kv(p_block, enc_out, cfg):
    """Precompute cross-attention K/V from encoder output (per layer)."""
    B, Se, _ = enc_out.shape
    hd = cfg.head_dim_r
    k = linear(p_block["xattn"], "wk", enc_out, cfg.mac, cfg.cdtype).reshape(
        B, Se, cfg.n_kv_p, hd)
    v = linear(p_block["xattn"], "wv", enc_out, cfg.mac, cfg.cdtype).reshape(
        B, Se, cfg.n_kv_p, hd)
    return k, v
