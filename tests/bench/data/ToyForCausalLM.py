"""ToyForCausalLM: an architecture module that the harness has never
seen, for the plug-in test (``tests/bench/test_bench_arch.py``): a dense
decoder like Qwen2 but with a head width of its own (``head_dim``, not
``hidden_size // num_attention_heads``), grouped K/V heads, no bias on
q, k and v, and an output head of its own (``lm_head``), untied from the
embedding.  The test copies it to ``bench/arch/`` under a checkout of its
own; nothing else in the harness names it.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from bench.reference import einsum, matmul, rms_norm, rope
from bench.roofline import Dims

KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta")


def shapes(c: dict) -> dict:
    L, d = int(c["num_hidden_layers"]), int(c["hidden_size"])
    h, hkv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd, ff, v = int(c["head_dim"]), int(c["intermediate_size"]), \
        int(c["vocab_size"])
    return {
        "embed": {"table": (v, d)},
        "final_norm_g": (d,),
        "lm_head": {"w": (d, v)},
        "stack": {
            "attn": {"wq": (L, d, h * hd), "wk": (L, d, hkv * hd),
                     "wv": (L, d, hkv * hd), "wo": (L, h * hd, d)},
            "mlp": {"wi": (L, d, ff), "wg": (L, d, ff), "wo": (L, ff, d)},
            "ln1_g": (L, d), "ln2_g": (L, d),
        },
    }


def dims(c: dict) -> Dims:
    L, d = int(c["num_hidden_layers"]), int(c["hidden_size"])
    h, hkv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd, ff, v = int(c["head_dim"]), int(c["intermediate_size"]), \
        int(c["vocab_size"])
    linears = ((d, h * hd), (d, hkv * hd), (d, hkv * hd), (h * hd, d),
               (d, ff), (d, ff), (ff, d))
    return Dims(layers=L, heads=h, kv_heads=hkv, head_dim=hd, kv_bytes=4,
                linears=linears,
                matmul_params=L * sum(k * n for k, n in linears) + d * v,
                windows=(None,) * L)


@functools.partial(jax.jit, static_argnames=("c_items", "mode"))
def _forward(params, tokens, n_valid, targets, *, c_items, mode):
    c = dict(c_items)
    h, hkv, hd = (int(c[k]) for k in ("num_attention_heads",
                                      "num_key_value_heads", "head_dim"))
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    T = tokens.shape[0]
    pos = jnp.arange(T)
    keep = (pos[:, None] >= pos[None, :]) & (pos < n_valid)[None, :]

    def layer(x, p):
        a = rms_norm(x, p["ln1_g"], eps)
        q = rope(matmul(a, p["attn"]["wq"], mode).reshape(T, h, hd), pos,
                 theta)
        k = rope(matmul(a, p["attn"]["wk"], mode).reshape(T, hkv, hd), pos,
                 theta)
        v = matmul(a, p["attn"]["wv"], mode).reshape(T, hkv, hd)
        s = einsum("qhd,khd->hqk", q * hd ** -0.5,
                   jnp.repeat(k, h // hkv, axis=1), mode)
        pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        att = einsum("hqk,khd->qhd", pr, jnp.repeat(v, h // hkv, axis=1),
                     mode).reshape(T, h * hd)
        x = x + matmul(att, p["attn"]["wo"], mode)
        m = rms_norm(x, p["ln2_g"], eps)
        up = matmul(m, p["mlp"]["wi"], mode)
        gate = matmul(m, p["mlp"]["wg"], mode)
        return x + matmul(jax.nn.silu(gate) * up, p["mlp"]["wo"], mode), \
            (k, v)

    x = params["embed"]["table"][tokens].astype(jnp.float32)
    x, (ks, vs) = jax.lax.scan(layer, x, params["stack"])
    lg = matmul(rms_norm(x, params["final_norm_g"], eps),
                params["lm_head"]["w"], mode)
    return (lg.max(-1), jnp.take_along_axis(lg, targets[:, None], 1)[:, 0],
            jnp.argmax(lg, -1).astype(jnp.int32), ks, vs)


class Reference:
    def __init__(self, c: dict, params: dict, *, mode: str = "highest",
                 bits: int = 0, amax=None):
        if bits:
            raise ValueError("ToyForCausalLM has no integer-code linears")
        self.params, self.mode = params, mode
        self.items = tuple((k, c[k]) for k in KEYS)

    def run(self, tokens, length, targets):
        n = len(tokens)
        tok = np.zeros((length,), np.int32)
        tok[:n] = tokens
        tgt = np.zeros((length,), np.int32)
        tgt[:len(targets)] = targets
        mx, tg, am, ks, vs = _forward(self.params, jnp.asarray(tok), n,
                                      jnp.asarray(tgt), c_items=self.items,
                                      mode=self.mode)
        return (np.asarray(mx)[:n], np.asarray(tg)[:n], np.asarray(am)[:n],
                ks, vs)
