"""Mixture-of-Experts with capacity-based dispatch and shard_map expert
parallelism over the model axis.

Routing (softmax or DeepSeek-style sigmoid) runs under plain pjit (sharded
over data); the expert FFN runs inside shard_map: tokens are replicated
across the model axis within a data shard, each model shard computes its
local experts over the tokens routed to them (static-capacity sort-based
dispatch), and contributions combine with a psum over 'model'.  Collective
cost == one (T_local, d) all-reduce per MoE layer, same order as TP-MLP.

Aux losses: standard load-balance (switch-style) for softmax routers; the
sigmoid router follows DeepSeek's bias-corrected aux-free scheme (bias is a
buffer updated outside grad; we expose the per-shard load for it).
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.parallel.sharding import (get_mesh, AXIS_BATCH,
                                     AXIS_MODEL)
from jax.sharding import PartitionSpec as P
from .common import linear, linear_init, mlp_init, mlp_apply, act_fn


def moe_init(key, cfg) -> dict:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 6)
    std = 1.0 / np.sqrt(d)
    p = {
        "router": {"w": (jax.random.normal(ks[0], (d, E), jnp.float32)
                         * std).astype(jnp.float32)},
        "experts_wi": (jax.random.normal(ks[1], (E, d, f), jnp.float32)
                       * std).astype(cfg.pdtype),
        "experts_wg": (jax.random.normal(ks[2], (E, d, f), jnp.float32)
                       * std).astype(cfg.pdtype),
        "experts_wo": (jax.random.normal(ks[3], (E, f, d), jnp.float32)
                       / np.sqrt(f)).astype(cfg.pdtype),
    }
    if cfg.router_type == "sigmoid":
        p["router"]["bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.n_shared_experts * f, cfg.mac,
                               gated=True, dtype=cfg.pdtype)
    return p


def route(p: dict, x2: jnp.ndarray, cfg):
    """Router → (topk_idx (T,k) i32, topk_w (T,k) f32, aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32))
    k = cfg.top_k
    if cfg.router_type == "sigmoid":          # DeepSeek-V3 aux-free
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router"]["bias"][None, :]
        _, idx = jax.lax.top_k(sel, k)
        w = jnp.take_along_axis(scores, idx, axis=1)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        aux = jnp.zeros((), jnp.float32)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        if cfg.norm_topk:
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        # switch-style load balance: E · Σ_e f_e · P̄_e
        E = cfg.n_experts
        dispatch = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
        f_e = dispatch.mean(0)
        aux = E * jnp.sum(f_e * probs.mean(0))
    return idx.astype(jnp.int32), w.astype(jnp.float32), aux


def _expert_ffn_local(xi, wg, wo, buf, act):
    h = jnp.einsum("ecd,edf->ecf", buf, xi,
                   preferred_element_type=jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", buf, wg,
                   preferred_element_type=jnp.float32)
    h = act_fn(act)(g) * h
    return jnp.einsum("ecf,efd->ecd", h.astype(xi.dtype), wo,
                      preferred_element_type=jnp.float32)


def dispatch_compute(x2, idx, w, wi, wg, wo, *, n_experts_total: int,
                     capacity: int, act: str, axis_name: Optional[str]):
    """Capacity-based sort dispatch + local expert FFN (+ psum combine).

    x2 (T,d) tokens; idx/w (T,k) routing; wi/wg/wo local expert stacks
    (E_local, …).  Inside shard_map, ``axis_name`` names the expert axis.
    """
    T, d = x2.shape
    k = idx.shape[1]
    E_local = wi.shape[0]
    if axis_name is not None:
        my = jax.lax.axis_index(axis_name)
        off = my * E_local
    else:
        off = 0

    eid = idx.reshape(-1)
    tid = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    wgt = w.reshape(-1)
    local = (eid >= off) & (eid < off + E_local)
    lid = jnp.clip(eid - off, 0, E_local - 1)
    key = jnp.where(local, lid, E_local)          # non-local sorts last
    order = jnp.argsort(key, stable=True)
    key_s, tid_s, wgt_s = key[order], tid[order], wgt[order]
    counts = jnp.bincount(key_s, length=E_local + 1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * k, dtype=jnp.int32) - starts[key_s]
    keep = (key_s < E_local) & (rank < capacity)
    slot = jnp.where(keep, key_s * capacity + rank, E_local * capacity)

    buf = jnp.zeros((E_local * capacity + 1, d), x2.dtype)
    buf = buf.at[slot].set(x2[tid_s])
    y = _expert_ffn_local(wi, wg, wo,
                          buf[:-1].reshape(E_local, capacity, d), act)
    y = jnp.concatenate([y.reshape(E_local * capacity, d).astype(jnp.float32),
                         jnp.zeros((1, d), jnp.float32)], 0)
    contrib = y[slot] * jnp.where(keep, wgt_s, 0.0)[:, None]
    out = jnp.zeros((T, d), jnp.float32).at[tid_s].add(contrib)
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return out


def moe_apply(p: dict, x: jnp.ndarray, cfg) -> tuple:
    """MoE FFN over x (B, S, d) → (out, aux_loss)."""
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    idx, w, aux = route(p, x2, cfg)

    mesh = get_mesh()
    ep = mesh is not None and AXIS_MODEL in mesh.axis_names \
        and cfg.n_experts % mesh.shape[AXIS_MODEL] == 0
    if ep:
        tp = mesh.shape[AXIS_MODEL]
        data_axes = tuple(a for a in AXIS_BATCH if a in mesh.axis_names)
        n_data = int(np.prod([mesh.shape[a] for a in data_axes]))
        t_local = (B * S) // max(n_data, 1)
        cap = max(4, int(cfg.capacity_factor * t_local * cfg.top_k
                         / cfg.n_experts))
        fn = functools.partial(dispatch_compute,
                               n_experts_total=cfg.n_experts, capacity=cap,
                               act=cfg.act, axis_name=AXIS_MODEL)
        out = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(data_axes, None), P(data_axes, None),
                      P(data_axes, None), P(AXIS_MODEL, None, None),
                      P(AXIS_MODEL, None, None), P(AXIS_MODEL, None, None)),
            out_specs=P(data_axes, None),
        )(x2, idx, w, p["experts_wi"], p["experts_wg"], p["experts_wo"])
    else:
        cap = max(4, int(cfg.capacity_factor * B * S * cfg.top_k
                         / cfg.n_experts))
        out = dispatch_compute(x2, idx, w, p["experts_wi"], p["experts_wg"],
                               p["experts_wo"],
                               n_experts_total=cfg.n_experts, capacity=cap,
                               act=cfg.act, axis_name=None)
    out = out.astype(cfg.cdtype)
    if cfg.n_shared_experts:
        out = out + mlp_apply(p["shared"], x2, cfg.mac, cfg.act, True,
                              cfg.cdtype)
    return out.reshape(B, S, d), aux
