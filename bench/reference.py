"""What every architecture's plain reference shares
(``bench/arch/<Architecture>.py`` holds each forward): matrix products
under a precision mode, RMSNorm, rotary positions, the padded length a
cell's sequences run at, and the calibration stream of the encoded
linears.  Straightforward ``jax.numpy`` in float32, with no kernel, cache,
paging or batching of requests, and nothing imported from the program.

``mode`` sets how every matrix product is computed:

  highest   float32 (``Precision.HIGHEST``): the reference
  bf16_3x   three bfloat16 passes (hi*hi + hi*lo + lo*hi), what a TPU's
            ``Precision.HIGH`` does: the control for a float32 model at
            ``highest``
  bf16      one bfloat16 pass: a TPU's default for float32
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


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


def padded_length(n: int, rows: int = 256) -> int:
    return -(-n // rows) * rows


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
