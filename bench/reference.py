"""Plain reference forward of a dense Qwen2-style decoder (Qwen1.5):
pre-norm RMSNorm blocks, rotary positions (rotate-half), bias on the q, k
and v projections, causal multi-head attention, a SwiGLU MLP and an output
head tied to the embedding.  Straightforward ``jax.numpy`` in float32,
with no kernel, cache, paging or batching of requests, and nothing
imported from the program.

``mode`` sets how every matrix product is computed:

  highest   float32 (``Precision.HIGHEST``): the reference
  bf16_3x   three bfloat16 passes (hi*hi + hi*lo + lo*hi), what a TPU's
            ``Precision.HIGH`` does: the control for a float32 model at
            ``highest``
  bf16      one bfloat16 pass: a TPU's default for float32

``quant`` turns the named linears into integer-code products, the
encoded MAC's arithmetic with the exact product circuit: symmetric codes
``clip(round(x / s), -q, q)`` with ``q = 2**(bits-1) - 1``, a static
activation scale ``s = amax / q`` per layer and linear from a calibration
stream, and a max-abs weight scale per layer and linear.  The products of
codes are summed exactly and scaled back by the product of the scales.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
# linears as (block, name), in the parameter layout of ``weights.shapes``
LINEARS = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
           ("mlp", "wi"), ("mlp", "wg"), ("mlp", "wo"))


def matmul(a, b, mode: str):
    """``a @ b`` over the last axis of a and the first of b, f32 out."""
    dims = (((a.ndim - 1,), (0,)), ((), ()))
    if mode == "highest":
        return jax.lax.dot_general(a, b, dims, precision=HI,
                                   preferred_element_type=jnp.float32)
    bf = jnp.bfloat16

    def dot(x, y):
        return jax.lax.dot_general(x.astype(bf), y.astype(bf), dims,
                                   preferred_element_type=jnp.float32)
    if mode == "bf16":
        return dot(a, b)
    if mode == "bf16_3x":
        a_hi, b_hi = a.astype(bf), b.astype(bf)
        a_lo = (a - a_hi.astype(jnp.float32)).astype(bf)
        b_lo = (b - b_hi.astype(jnp.float32)).astype(bf)
        return dot(a_hi, b_lo) + dot(a_lo, b_hi) + dot(a_hi, b_hi)
    raise ValueError(f"unknown matmul mode {mode!r}")


def einsum(spec: str, a, b, mode: str):
    """Two-operand einsum under ``mode`` (same passes as ``matmul``)."""
    if mode == "highest":
        return jnp.einsum(spec, a, b, precision=HI,
                          preferred_element_type=jnp.float32)
    bf = jnp.bfloat16

    def e(x, y):
        return jnp.einsum(spec, x.astype(bf), y.astype(bf),
                          preferred_element_type=jnp.float32)
    if mode == "bf16":
        return e(a, b)
    a_hi, b_hi = a.astype(bf), b.astype(bf)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(bf)
    b_lo = (b - b_hi.astype(jnp.float32)).astype(bf)
    return e(a_hi, b_lo) + e(a_lo, b_hi) + e(a_hi, b_hi)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, positions, theta):
    """Rotate-half rotary embedding; x (T, H, D), positions (T,)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def padded_length(n: int, rows: int = 256) -> int:
    return -(-n // rows) * rows


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


# ---------------------------------------------------------------------------
# calibration stream (the activation scales of the encoded linears)
# ---------------------------------------------------------------------------

def calibration_tokens(vocab: int, seq_len: int, seed: int, n_batches: int,
                       batch_size: int):
    """The serving calibration's synthetic token stream: a sparse bigram
    Markov chain (4 likely followers per token, 5% uniform noise), batch
    ``step`` drawn from ``(seed * 1000003 + step) * 65537``."""
    rng = np.random.default_rng(seed)
    next_tok = rng.integers(0, vocab, size=(vocab, 4))
    for step in range(n_batches):
        r = np.random.default_rng((seed * 1_000_003 + step) * 65_537)
        toks = np.empty((batch_size, seq_len + 1), np.int32)
        toks[:, 0] = r.integers(0, vocab, batch_size)
        choice = r.integers(0, 4, size=(batch_size, seq_len))
        noise = r.random((batch_size, seq_len)) < 0.05
        rand = r.integers(0, vocab, size=(batch_size, seq_len))
        for t in range(seq_len):
            nxt = next_tok[toks[:, t], choice[:, t]]
            toks[:, t + 1] = np.where(noise[:, t], rand[:, t], nxt)
        yield toks[:, :-1]


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
