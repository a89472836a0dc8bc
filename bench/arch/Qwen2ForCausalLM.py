"""Qwen2ForCausalLM (Qwen1.5): a dense decoder of pre-norm RMSNorm
blocks, rotary positions (rotate-half), bias on the q, k and v
projections, causal multi-head attention with grouped K/V heads, a SwiGLU
MLP and an output head tied to the embedding.  The head width is
``hidden_size // num_attention_heads``, as Qwen2's ``config.json`` gives
no ``head_dim``.

``Reference`` is the plain forward in float32 (``bench/reference.py``
for ``mode``).  With ``bits`` and ``amax`` the named linears become
integer-code products, the encoded MAC's arithmetic with the exact
product circuit: symmetric codes ``clip(round(x / s), -q, q)`` with
``q = 2**(bits-1) - 1``, a static activation scale ``s = amax / q`` per
layer and linear from a calibration stream (``calibrate``), and a max-abs
weight scale per layer and linear.  The products of codes are summed
exactly and scaled back by the product of the scales.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from bench.reference import calibration_tokens, einsum, matmul, rms_norm, \
    rope
from bench.roofline import Dims

# linears as (block, name), in the parameter layout of ``shapes``
LINEARS = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
           ("mlp", "wi"), ("mlp", "wg"), ("mlp", "wo"))


def shapes(c: dict) -> dict:
    """Leaf shapes of a dense Qwen2-style decoder, layers stacked first."""
    L, d = int(c["num_hidden_layers"]), int(c["hidden_size"])
    h, hkv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd, ff, v = d // h, int(c["intermediate_size"]), int(c["vocab_size"])
    return {
        "embed": {"table": (v, d)},
        "final_norm_g": (d,),
        "stack": {
            "attn": {"wq": (L, d, h * hd), "wq_b": (L, h * hd),
                     "wk": (L, d, hkv * hd), "wk_b": (L, hkv * hd),
                     "wv": (L, d, hkv * hd), "wv_b": (L, hkv * hd),
                     "wo": (L, h * hd, d)},
            "mlp": {"wi": (L, d, ff), "wg": (L, d, ff), "wo": (L, ff, d)},
            "ln1_g": (L, d), "ln2_g": (L, d),
        },
    }


def dims(c: dict) -> Dims:
    """Every layer alike, full attention; a token multiplies every
    layer's linears and the tied output head."""
    layers, d = int(c["num_hidden_layers"]), int(c["hidden_size"])
    heads, kv_heads = int(c["num_attention_heads"]), \
        int(c["num_key_value_heads"])
    hd, ff = d // heads, int(c["intermediate_size"])
    linears = ((d, heads * hd), (d, kv_heads * hd), (d, kv_heads * hd),
               (heads * hd, d), (d, ff), (d, ff), (ff, d))
    return Dims(layers=layers, heads=heads, kv_heads=kv_heads, head_dim=hd,
                kv_bytes=4 if c["torch_dtype"] == "float32" else 2,
                linears=linears,
                matmul_params=layers * sum(k * n for k, n in linears)
                + d * int(c["vocab_size"]),
                windows=(None,) * layers)


def _codes(x, s, q):
    return jnp.clip(jnp.round(x / s), -q, q)


def qlinear(x, w, amax, bits: int, mode: str):
    """Integer-code product of x (T, k) and w (k, n), scaled back."""
    q = float(2 ** (bits - 1) - 1)
    sa = jnp.maximum(amax, 1e-8) / q
    sw = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8) / q
    acc = matmul(_codes(x, sa, q), _codes(w, sw, q), mode)
    return acc * (sa * sw)


def _block(c, mode, bits, x, p, amax, positions, valid):
    """One decoder layer over x (T, d).  Returns the new x, this layer's
    K and V (after rotary), and the max |input| of each linear."""
    T, d = x.shape
    h, hkv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd = d // h
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    seen = {}

    def lin(block, name, inp):
        seen[(block, name)] = jnp.max(jnp.abs(inp))
        w = p[block][name]
        if bits and amax is not None:
            out = qlinear(inp, w, amax[(block, name)], bits, mode)
        else:
            out = matmul(inp, w, mode)
        b = p[block].get(name + "_b")
        return out if b is None else out + b

    a_in = rms_norm(x, p["ln1_g"], eps)
    q = rope(lin("attn", "wq", a_in).reshape(T, h, hd), positions, theta)
    k = rope(lin("attn", "wk", a_in).reshape(T, hkv, hd), positions, theta)
    v = lin("attn", "wv", a_in).reshape(T, hkv, hd)
    group = h // hkv
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    s = einsum("qhd,khd->hqk", q * (hd ** -0.5), kq, mode)
    causal = positions[:, None] >= positions[None, :]
    s = jnp.where(causal & valid[None, :], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    att = einsum("hqk,khd->qhd", pr, vq, mode).reshape(T, h * hd)
    x = x + lin("attn", "wo", att)
    m_in = rms_norm(x, p["ln2_g"], eps)
    up = lin("mlp", "wi", m_in)
    gate = lin("mlp", "wg", m_in)
    x = x + lin("mlp", "wo", jax.nn.silu(gate) * up)
    return x, k, v, seen


@functools.partial(jax.jit, static_argnames=("c_items", "mode", "bits"))
def _stack(params, tokens, n_valid, amax, *, c_items, mode, bits):
    """Embedding and every layer for one sequence, padded to a fixed
    length; positions >= n_valid are padding (masked as keys)."""
    c = dict(c_items)
    T = tokens.shape[0]
    positions = jnp.arange(T)
    valid = positions < n_valid
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    st = params["stack"]

    def body(x, layer):
        p, am = layer
        x, k, v, seen = _block(c, mode, bits, x, p, am, positions, valid)
        return x, (k, v, seen)

    x, (ks, vs, seen) = jax.lax.scan(body, x, (st, amax))
    return x, ks, vs, seen


@functools.partial(jax.jit, static_argnames=("c_items", "mode", "rows"))
def _head(params, x, targets, *, c_items, mode, rows=256):
    """Per position: the largest logit, the logit of ``targets`` and the
    argmax, in blocks of ``rows`` positions (never all logits at once)."""
    c = dict(c_items)
    h = rms_norm(x, params["final_norm_g"], float(c["rms_norm_eps"]))
    table = params["embed"]["table"]
    T = h.shape[0]
    hb = h.reshape(T // rows, rows, -1)
    tb = targets.reshape(T // rows, rows)

    def one(args):
        hh, tt = args
        lg = matmul(hh, table.T, mode)
        return (lg.max(-1), jnp.take_along_axis(lg, tt[:, None], 1)[:, 0],
                jnp.argmax(lg, -1).astype(jnp.int32))

    mx, tg, am = jax.lax.map(one, (hb, tb))
    return mx.reshape(T), tg.reshape(T), am.reshape(T)


def config_items(c: dict) -> tuple:
    """The configuration's scalar sizes as a hashable static argument."""
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta", "hidden_size")
    return tuple((k, c[k]) for k in keys)


class Reference:
    """The reference (or, with another ``mode`` or ``bits``, its control)
    for one configuration and one set of weights."""

    def __init__(self, c: dict, params: dict, *, mode: str = "highest",
                 bits: int = 0, amax: Optional[dict] = None):
        self.c, self.params, self.mode, self.bits = c, params, mode, bits
        self.amax = amax
        self.items = config_items(c)

    def _amax_tree(self):
        L = int(self.c["num_hidden_layers"])
        if self.amax is None:
            return {k: jnp.zeros((L,), jnp.float32) for k in LINEARS}
        return {k: jnp.asarray(v, jnp.float32) for k, v in self.amax.items()}

    def run(self, tokens: np.ndarray, length: int, targets: np.ndarray
            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, jnp.ndarray,
                       jnp.ndarray]:
        """One sequence padded to ``length``.  Returns per position the
        largest logit, the logit of ``targets[pos]``, the argmax, and the
        stacked K and V (L, length, kv heads, head dim) on the device."""
        n = len(tokens)
        tok = np.zeros((length,), np.int32)
        tok[:n] = tokens
        tgt = np.zeros((length,), np.int32)
        tgt[:len(targets)] = targets
        x, ks, vs, _ = _stack(self.params, jnp.asarray(tok), n,
                              self._amax_tree(), c_items=self.items,
                              mode=self.mode,
                              bits=self.bits if self.amax is not None else 0)
        mx, tg, am = _head(self.params, x, jnp.asarray(tgt),
                           c_items=self.items, mode=self.mode)
        return (np.asarray(mx)[:n], np.asarray(tg)[:n], np.asarray(am)[:n],
                ks, vs)


def calibrate(c: dict, params: dict, *, seq_len: int, seed: int,
              n_batches: int, batch_size: int) -> Dict[tuple, np.ndarray]:
    """Max |input| of every linear of every layer over the calibration
    stream, from the float32 forward: {(block, name): (layers,)}."""
    ref = Reference(c, params)
    L = int(c["num_hidden_layers"])
    amax = {k: np.zeros((L,), np.float32) for k in LINEARS}
    for batch in calibration_tokens(int(c["vocab_size"]), seq_len, seed,
                                    n_batches, batch_size):
        for row in batch:
            _, _, _, seen = _stack(params, jnp.asarray(row), len(row),
                                   ref._amax_tree(), c_items=ref.items,
                                   mode="highest", bits=0)
            for k in LINEARS:
                amax[k] = np.maximum(amax[k], np.asarray(seen[k]))
    return amax
