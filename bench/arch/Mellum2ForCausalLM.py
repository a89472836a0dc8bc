"""Mellum2ForCausalLM (Mellum 2): a decoder of pre-norm RMSNorm blocks
whose every feed-forward is a sparse expert layer, with grouped-query
attention that alternates ``layer_types`` (sliding-window and full
layers) and takes its rotary positions per layer kind from
``rope_parameters``: yarn on the full layers, plain rotary on the
window ones.  No bias on q, k and v; an output head of its own.

Per layer: ``x += wo · attn(rope(wq·n1), rope(wk·n1), wv·n1)`` with
n1 = RMSNorm(x), keys of a window layer limited to ``q_pos - k_pos <
sliding_window``; then ``x += Σ_j g_j · E_j(n2)`` with n2 = RMSNorm(x),
the router's softmax over every expert, its ``num_experts_per_tok``
largest probabilities renormalised to sum to 1 (``norm_topk_prob``), and
``E(y) = wo · (silu(wg · y) * (wi · y))``.

Yarn, as transformers' ``_compute_yarn_parameters`` gives it: where the
full layers' parameters name ``original_max_position_embeddings`` the
scaling factor is ``max_position_embeddings`` over it; dimensions that
turn more than ``beta_fast`` times over the original context keep their
frequency, those that turn fewer than ``beta_slow`` times are divided by
the factor, a linear ramp joins them; ``attention_factor`` multiplies
cos and sin (so the logits by its square).

``Reference`` is the plain forward in float32 (``bench/reference.py``
for ``mode``): attention in blocks of query positions, and every expert
computed for every position in blocks of positions, weighted by the
gates (zero for the experts a position is not routed to), so that a
sample of 5,120 positions fits beside the weights.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from bench.reference import einsum, matmul, rms_norm
from bench.roofline import Dims

FULL, SLIDING = "full_attention", "sliding_attention"
NO_WINDOW = 2 ** 30
BLOCK = 256                      # positions per attention / expert block


def _sizes(c: dict) -> Tuple[int, ...]:
    return tuple(int(c[k]) for k in (
        "num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size", "vocab_size"))


def layer_kinds(c: dict) -> Tuple[str, ...]:
    """The kind of each layer held: the published pattern's first
    ``num_hidden_layers`` entries."""
    return tuple(c["layer_types"][:int(c["num_hidden_layers"])])


def shapes(c: dict) -> dict:
    """Leaf shapes in the program's layout: one stage of expert layers,
    layers stacked first."""
    L, d, h, hkv, hd, E, _, f, v = _sizes(c)
    return {
        "embed": {"table": (v, d)},
        "final_norm_g": (d,),
        "lm_head": {"w": (d, v)},
        "moe_stack": {
            "attn": {"wq": (L, d, h * hd), "wk": (L, d, hkv * hd),
                     "wv": (L, d, hkv * hd), "wo": (L, h * hd, d)},
            "moe": {"router": {"w": (L, d, E)},
                    "experts_wi": (L, E, d, f), "experts_wg": (L, E, d, f),
                    "experts_wo": (L, E, f, d)},
            "ln1_g": (L, d), "ln2_g": (L, d),
        },
    }


def dims(c: dict) -> Dims:
    """Head width from ``head_dim``; a token multiplies q, k, v, o, the
    router and its routed experts' three linears in every layer, and the
    output head; window layers attend ``sliding_window`` keys."""
    L, d, h, hkv, hd, E, k, f, v = _sizes(c)
    linears = ((d, h * hd), (d, hkv * hd), (d, hkv * hd), (h * hd, d),
               (d, E)) + ((d, f), (d, f), (f, d)) * k
    return Dims(layers=L, heads=h, kv_heads=hkv, head_dim=hd,
                kv_bytes=4 if c["torch_dtype"] == "float32" else 2,
                linears=linears,
                matmul_params=L * sum(a * b for a, b in linears) + d * v,
                windows=tuple(int(c["sliding_window"]) if t == SLIDING
                              else None for t in layer_kinds(c)))


# ---------------------------------------------------------------------------
# rotary positions per layer kind
# ---------------------------------------------------------------------------

def _plain_inv_freq(hd: int, theta: float) -> np.ndarray:
    return 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)


def yarn(c: dict) -> Tuple[np.ndarray, float]:
    """(inverse frequencies (head_dim/2,) float32, attention factor) of
    the full layers, as transformers computes them from the config."""
    p = c["rope_parameters"][FULL]
    hd = int(c["head_dim"])
    base = float(p["rope_theta"])
    factor = float(p["factor"])
    original = int(p.get("original_max_position_embeddings") or
                   c["max_position_embeddings"])
    if "original_max_position_embeddings" in p:
        factor = int(c["max_position_embeddings"]) / original
    att = p.get("attention_factor")
    if att is None:
        att = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    beta_fast = float(p.get("beta_fast") or 32)
    beta_slow = float(p.get("beta_slow") or 1)

    def correction_dim(rotations):
        return hd * math.log(original / (rotations * 2 * math.pi)) / \
            (2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                    # share at the base frequency
    pos = _plain_inv_freq(hd, base)
    inv = pos / factor * (1.0 - keep) + pos * keep
    return inv.astype(np.float32), float(att)


def rope_tables(c: dict):
    """Per layer held: inverse frequencies (L, head_dim/2), cos/sin scale
    (L,) and the attention window (L,) (``NO_WINDOW`` on full layers)."""
    hd = int(c["head_dim"])
    inv_y, att = yarn(c)
    p = c["rope_parameters"][SLIDING]
    if p.get("rope_type", "default") != "default":
        raise ValueError("sliding layers: only plain rotary is written here")
    inv_s = _plain_inv_freq(hd, float(p["rope_theta"])).astype(np.float32)
    kinds = layer_kinds(c)
    inv = np.stack([inv_y if t == FULL else inv_s for t in kinds])
    scale = np.asarray([att if t == FULL else 1.0 for t in kinds],
                       np.float32)
    win = np.asarray([NO_WINDOW if t == FULL else int(c["sliding_window"])
                      for t in kinds], np.int32)
    return jnp.asarray(inv), jnp.asarray(scale), jnp.asarray(win)


def _rope(x, positions, inv, scale):
    """Rotate-half rotary embedding; x (T, H, D), cos and sin scaled."""
    ang = positions[:, None].astype(jnp.float32) * inv
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

def _attention(c, mode, q, k, v, positions, valid, window):
    """Causal grouped-query attention over (T, heads, hd), in blocks of
    query positions."""
    T, h, hd = q.shape
    hkv = k.shape[1]
    kq = jnp.repeat(k, h // hkv, axis=1)
    vq = jnp.repeat(v, h // hkv, axis=1)

    def block(args):
        qb, pb = args
        s = einsum("qhd,khd->hqk", qb * (hd ** -0.5), kq, mode)
        d = pb[:, None] - positions[None, :]
        keep = (d >= 0) & (d < window) & valid[None, :]
        # finite: a padding query's window may hold no valid key
        pr = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        return einsum("hqk,khd->qhd", pr, vq, mode)

    out = jax.lax.map(block, (q.reshape(T // BLOCK, BLOCK, h, hd),
                              positions.reshape(T // BLOCK, BLOCK)))
    return out.reshape(T, h * hd)


def _experts(c, mode, y, p):
    """The sparse layer over y (T, d): softmax router, top-k gates
    renormalised, every expert computed in blocks of positions and
    weighted by its gate (0 where not routed)."""
    T, d = y.shape
    k = int(c["num_experts_per_tok"])
    probs = jax.nn.softmax(matmul(y, p["router"]["w"], mode), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if c.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(T)[:, None], idx].set(top)               # (T, E)

    def block(args):
        yb, gb = args
        up = einsum("td,edf->etf", yb, p["experts_wi"], mode)
        gate = einsum("td,edf->etf", yb, p["experts_wg"], mode)
        out = einsum("etf,efd->etd", jax.nn.silu(gate) * up,
                     p["experts_wo"], mode)
        return einsum("te,etd->td", gb, out, mode)

    out = jax.lax.map(block, (y.reshape(T // BLOCK, BLOCK, d),
                              gates.reshape(T // BLOCK, BLOCK, -1)))
    return out.reshape(T, d)


@functools.partial(jax.jit, static_argnames=("c_items", "mode"))
def _stack(params, tokens, n_valid, tables, *, c_items, mode):
    """Embedding and every layer for one sequence padded to a multiple of
    ``BLOCK``; positions >= n_valid are padding (masked as keys)."""
    c = dict(c_items)
    h, hkv, hd = (int(c[k]) for k in ("num_attention_heads",
                                      "num_key_value_heads", "head_dim"))
    eps = float(c["rms_norm_eps"])
    T = tokens.shape[0]
    positions = jnp.arange(T)
    valid = positions < n_valid
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def body(x, layer):
        p, inv, scale, window = layer
        a = rms_norm(x, p["ln1_g"], eps)
        q = _rope(matmul(a, p["attn"]["wq"], mode).reshape(T, h, hd),
                  positions, inv, scale)
        kk = _rope(matmul(a, p["attn"]["wk"], mode).reshape(T, hkv, hd),
                   positions, inv, scale)
        v = matmul(a, p["attn"]["wv"], mode).reshape(T, hkv, hd)
        att = _attention(c, mode, q, kk, v, positions, valid, window)
        x = x + matmul(att, p["attn"]["wo"], mode)
        x = x + _experts(c, mode, rms_norm(x, p["ln2_g"], eps), p["moe"])
        return x, (kk, v)

    x, (ks, vs) = jax.lax.scan(body, x, (params["moe_stack"],) + tables)
    return x, ks, vs


@functools.partial(jax.jit, static_argnames=("c_items", "mode"))
def _head(params, x, targets, *, c_items, mode):
    """Per position: the largest logit, the logit of ``targets`` and the
    argmax, in blocks of ``BLOCK`` positions."""
    c = dict(c_items)
    hn = rms_norm(x, params["final_norm_g"], float(c["rms_norm_eps"]))
    T = hn.shape[0]

    def one(args):
        hh, tt = args
        lg = matmul(hh, params["lm_head"]["w"], mode)
        return (lg.max(-1), jnp.take_along_axis(lg, tt[:, None], 1)[:, 0],
                jnp.argmax(lg, -1).astype(jnp.int32))

    mx, tg, am = jax.lax.map(one, (hn.reshape(T // BLOCK, BLOCK, -1),
                                   targets.reshape(T // BLOCK, BLOCK)))
    return mx.reshape(T), tg.reshape(T), am.reshape(T)


def config_items(c: dict) -> tuple:
    """The configuration's scalars the forward reads, hashable."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "num_experts_per_tok", "norm_topk_prob")
    return tuple((k, c[k]) for k in keys)


class Reference:
    """The reference (or, with another ``mode``, its control) for one
    configuration and one set of weights."""

    def __init__(self, c: dict, params: dict, *, mode: str = "highest",
                 bits: int = 0, amax=None):
        if bits or amax is not None:
            raise ValueError("Mellum2ForCausalLM has no encoded linears")
        self.c, self.params, self.mode = c, params, mode
        self.items = config_items(c)
        self.tables = rope_tables(c)

    def run(self, tokens: np.ndarray, length: int, targets: np.ndarray):
        """One sequence padded to ``length`` (a multiple of ``BLOCK``).
        Returns per position the largest logit, the logit of
        ``targets[pos]``, the argmax, and the stacked K and V (layers,
        length, kv heads, head dim) on the device."""
        n = len(tokens)
        tok = np.zeros((length,), np.int32)
        tok[:n] = tokens
        tgt = np.zeros((length,), np.int32)
        tgt[:len(targets)] = targets
        x, ks, vs = _stack(self.params, jnp.asarray(tok), n, self.tables,
                           c_items=self.items, mode=self.mode)
        mx, tg, am = _head(self.params, x, jnp.asarray(tgt),
                           c_items=self.items, mode=self.mode)
        return (np.asarray(mx)[:n], np.asarray(tg)[:n], np.asarray(am)[:n],
                ks, vs)
