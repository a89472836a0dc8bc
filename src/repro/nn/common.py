"""Shared NN primitives: linears (fp / int8 / encoded-MAC), norms, embeddings,
rotary, MLPs.  Functional style — params are nested dicts of arrays; naming
follows parallel/sharding.py rules (e.g. 'wq', 'wi', 'wo', 'norm_*')."""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.layers import MacConfig
from repro.core.macexec import mm


# Serving-calibration hook (DESIGN.md §3): when set, ``linear`` reports every
# call as (name, weight, input) before computing.  Installed only by the
# eager, unrolled calibration forward (repro.serve.encoded) — the plain None
# check is free on the jitted paths.
_ACT_RECORDER = None


def set_activation_recorder(fn):
    """Install/remove the calibration recorder; returns the previous hook."""
    global _ACT_RECORDER
    prev, _ACT_RECORDER = _ACT_RECORDER, fn
    return prev


def linear_init(key, d_in: int, d_out: int, name: str, mcfg: MacConfig,
                bias: bool = False, dtype=jnp.float32, scale: float = None
                ) -> dict:
    """Init a named linear: the MAC executor owns the weight + its suffix
    schema (DESIGN.md §6); the shared ``_b`` bias is mode-independent."""
    p = mcfg.executor.init(key, d_in, d_out, name, mcfg, dtype=dtype,
                           scale=scale)
    if bias:
        p[name + "_b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: dict, name: str, x: jnp.ndarray, mcfg: MacConfig,
           compute_dtype=jnp.float32) -> jnp.ndarray:
    """Apply a named linear: recorder hook + MAC-executor dispatch
    (DESIGN.md §6) + bias.  All mode-specific behaviour (quantization,
    encoded kernels, folded-tensor serving, TP roles) lives in the
    registered executor, not here."""
    if _ACT_RECORDER is not None:
        _ACT_RECORDER(name, p[name], x)
    out = mcfg.executor.apply(p, name, x, mcfg, compute_dtype)
    if name + "_b" in p:
        out = out + p[name + "_b"].astype(out.dtype)
    return out


# --- norms ------------------------------------------------------------------

def norm_init(d: int, kind: str = "rms", dtype=jnp.float32, name="norm"
              ) -> dict:
    p = {name + "_g": jnp.ones((d,), dtype)}
    if kind == "layer":
        p[name + "_bln"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p: dict, x: jnp.ndarray, kind: str = "rms",
               eps: float = 1e-6, name="norm") -> jnp.ndarray:
    """Stats in f32 via contractions (no materialized f32 (B,S,d) squares —
    §Perf iter 3: cuts per-layer logical HBM bytes); scale applied in the
    compute dtype.  f32 inputs keep full-f32 behaviour bit-for-bit."""
    if x.dtype == jnp.float32:
        xf = x
        if kind == "rms":
            xn = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                    + eps)
            return xn * p[name + "_g"].astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        return (xf - mu) * jax.lax.rsqrt(var + eps) \
            * p[name + "_g"].astype(jnp.float32) \
            + p[name + "_bln"].astype(jnp.float32)
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    ssq = jnp.einsum("...d,...d->...", xf, xf,
                     preferred_element_type=jnp.float32) / d
    if kind == "rms":
        r = jax.lax.rsqrt(ssq + eps)
        return (x * r[..., None].astype(x.dtype)) \
            * p[name + "_g"].astype(x.dtype)
    mu = jnp.mean(xf, -1)
    var = jnp.maximum(ssq - mu * mu, 0.0)
    r = jax.lax.rsqrt(var + eps)
    out = (x - mu[..., None].astype(x.dtype)) * r[..., None].astype(x.dtype)
    return out * p[name + "_g"].astype(x.dtype) \
        + p[name + "_bln"].astype(x.dtype)


# --- embeddings --------------------------------------------------------------

def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32)
                      * 0.02).astype(dtype)}


def embed_apply(p: dict, ids: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    return p["table"].astype(compute_dtype)[ids]


# --- rotary -------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's inverse frequencies (D/2,) float32, as transformers'
    ``_compute_yarn_parameters`` computes them (``truncate`` on): the
    dimensions that turn fewer than ``beta_slow`` times over the original
    context are interpolated by ``factor``, those that turn more than
    ``beta_fast`` times keep their frequency, and a linear ramp joins
    them."""
    d = head_dim
    pos_freqs = theta ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def corr_dim(rot):
        return d * np.log(original_max / (rot * 2 * np.pi)) / \
            (2 * np.log(theta))
    low = max(np.floor(corr_dim(beta_fast)), 0)
    high = min(np.ceil(corr_dim(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    extrap = 1.0 - ramp                 # share kept at the base frequency
    inv = (1.0 / (factor * pos_freqs)) * (1 - extrap) + \
        (1.0 / pos_freqs) * extrap
    return inv.astype(np.float32)


def layer_rope(cfg, full):
    """(inv_freq (D/2,), cos/sin scale) of a layer: yarn's on a
    full-attention layer (``full``: a bool, or a traced one read from the
    scanned per-layer window), plain ``rope_theta`` otherwise."""
    d = cfg.head_dim_r
    plain = (1.0 / cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64)
                                      / d)).astype(np.float32)
    yarn = yarn_inv_freq(d, cfg.rope_theta, cfg.rope_yarn_factor,
                         cfg.rope_yarn_original_max, cfg.rope_yarn_beta_fast,
                         cfg.rope_yarn_beta_slow)
    mscale = cfg.rope_yarn_attention_factor
    if mscale is None:
        mscale = 0.1 * np.log(cfg.rope_yarn_factor) + 1.0
    return (jnp.where(full, yarn, plain),
            jnp.where(full, np.float32(mscale), np.float32(1.0)))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = None,
               *, inv_freq=None, scale=None) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (S,).  Frequencies from
    ``theta``, or ``inv_freq`` (D/2,) as given; ``scale`` multiplies cos
    and sin (yarn's attention factor)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta) if inv_freq is None else inv_freq
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# --- MLP ----------------------------------------------------------------------

def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
            "relu": jax.nn.relu}[name]


def mlp_init(key, d: int, d_ff: int, mcfg: MacConfig, gated: bool = True,
             bias: bool = False, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    p = {}
    p.update(linear_init(ks[0], d, d_ff, "wi", mcfg, bias, dtype))
    if gated:
        p.update(linear_init(ks[1], d, d_ff, "wg", mcfg, False, dtype))
    p.update(linear_init(ks[2], d_ff, d, "wo", mcfg, bias, dtype))
    return p


def mlp_apply(p: dict, x: jnp.ndarray, mcfg: MacConfig, act: str = "silu",
              gated: bool = True, compute_dtype=jnp.float32) -> jnp.ndarray:
    h = linear(p, "wi", x, mcfg, compute_dtype)
    if gated:
        h = act_fn(act)(linear(p, "wg", x, mcfg, compute_dtype)) * h
    else:
        h = act_fn(act)(h)
    return linear(p, "wo", h, mcfg, compute_dtype)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)
