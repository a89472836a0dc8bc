"""Mixture-of-Experts with no-drop routing and shard_map expert
parallelism over the model axis.

Routing (softmax or DeepSeek-style sigmoid) runs under plain pjit (sharded
over data); the expert FFN runs inside shard_map: tokens are replicated
across the model axis within a data shard, each model shard holds
``E / n_model`` experts (the first at ``axis_index · E_local``) and
computes every (token, expert) pair routed to one of them — no capacity,
nothing dropped — and contributions combine with a psum over 'model'.
Collective cost == one (T_local, d) all-reduce per MoE layer, same order
as TP-MLP.  On one device the layer holds every expert and runs without
the exchange.

Expert compute: the assignments are sorted by local expert and each
projection is one grouped matmul over the held experts
(``grouped_matmul``): on a TPU the megablox Pallas kernel under the
jitted name ``moe_experts`` (its device ops are ``%moe_experts.<n>`` in a
trace, three per layer and dispatch), elsewhere ``lax.ragged_dot``;
gradients go through ``ragged_dot``'s rules on either.

Aux losses: standard load-balance (switch-style) for softmax routers; the
sigmoid router follows DeepSeek's bias-corrected aux-free scheme (bias is a
buffer updated outside grad; we expose the per-shard load for it).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm

from repro.parallel.sharding import (get_mesh, AXIS_BATCH,
                                     AXIS_MODEL)
from jax.sharding import PartitionSpec as P
from .common import mlp_init, mlp_apply, act_fn

# the expert weights, (E, …) a layer; a layer stack passes them whole
# (L, E, …) with the layer's index (``moe_apply``'s ``layer``)
EXPERT_STACKS = ("experts_wi", "experts_wg", "experts_wo")


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


# ---------------------------------------------------------------------------
# grouped matmul over the held experts
# ---------------------------------------------------------------------------

def _tile(n: int, cap: int = 1024) -> int:
    """A block width for a dimension of ``n``: the largest multiple of 128
    up to ``cap`` that divides it, else ``n`` itself where it fits."""
    for t in range(cap // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n if n <= cap else 128


@functools.partial(jax.jit, static_argnames=("tiling",))
def moe_experts(x, w, group_sizes, *, tiling):
    """The megablox grouped-matmul kernel, traced under this jitted name:
    its Pallas call takes the name of the jitted function around it, so
    the device op is ``%moe_experts.<n>`` (pinned in
    ``tests/test_tpu_compile.py``; the expert roofline finds it by it)."""
    return _gmm.__wrapped__(x, w, group_sizes,
                            preferred_element_type=jnp.float32,
                            tiling=tiling)


def _grouped_tpu(x, w, group_sizes):
    m, k = x.shape
    tm = 128 if m >= 2048 else 64      # decode: fewer rows per expert
    mp = -(-m // tm) * tm
    xp = jnp.pad(x, ((0, mp - m), (0, 0)))
    out = moe_experts(xp, w, group_sizes,
                      tiling=(tm, _tile(k), _tile(w.shape[2])))
    return out[:m]


def _ragged(x, w, group_sizes):
    return jax.lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32)


@jax.custom_vjp
def grouped_matmul(x, w, group_sizes):
    """Rows of ``x`` (m, k), sorted by group, each times its group's
    ``w`` (G, k, n) → (m, n) f32; group g holds ``group_sizes[g]`` rows
    and rows past their sum are left undefined.  The platform picks the
    lowering (the Pallas kernel on a TPU)."""
    return jax.lax.platform_dependent(x, w, group_sizes, tpu=_grouped_tpu,
                                      default=_ragged)


def _grouped_fwd(x, w, group_sizes):
    return grouped_matmul(x, w, group_sizes), (x, w, group_sizes)


def _grouped_bwd(res, g):
    x, w, group_sizes = res
    _, vjp = jax.vjp(lambda x, w: _ragged(x, w, group_sizes), x, w)
    dx, dw = vjp(g)
    return dx, dw, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def dispatch_compute(x2, idx, w, wi, wg, wo, *, act: str, offset=0,
                     layer=None):
    """No-drop sort dispatch + the held experts' FFN.

    x2 (T,d) tokens; idx/w (T,k) routing over every expert; wi/wg/wo the
    stacks (E_local, …) of the experts held here, the first of them
    expert ``offset`` — or, with ``layer``, every layer's (L, E_local, …),
    of which layer ``layer``'s are read in place.  Every (token, expert)
    pair routed to a held expert is computed.  Returns (out (T, d) f32,
    the part of the layer's output these experts give; stats (2,) f32:
    the assignments computed here and the held experts that got at least
    one)."""
    T, d = x2.shape
    k = idx.shape[1]
    E_local = wi.shape[-3]
    eid = idx.reshape(-1)
    tid = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    local = (eid >= offset) & (eid < offset + E_local)
    key = jnp.where(local, eid - offset, E_local)    # non-local sorts last
    order = jnp.argsort(key, stable=True)
    key_s, tid_s, wgt_s = key[order], tid[order], w.reshape(-1)[order]
    sizes = jnp.bincount(key_s, length=E_local + 1)[:E_local]
    sizes = sizes.astype(jnp.int32)
    groups = sizes
    if layer is not None:
        # the layer's experts are groups layer·E_local … of the flattened
        # stack (a free reshape); every other layer's groups are empty
        L = wi.shape[0]
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E_local,), jnp.int32), sizes,
            (layer * E_local,))
        wi, wg, wo = (a.reshape(L * E_local, *a.shape[2:])
                      for a in (wi, wg, wo))

    xs = x2[tid_s]
    h = act_fn(act)(grouped_matmul(xs, wg, groups)) \
        * grouped_matmul(xs, wi, groups)
    y = grouped_matmul(h.astype(wo.dtype), wo, groups)
    # rows of non-local experts hold no result: select, never multiply
    contrib = jnp.where((key_s < E_local)[:, None], y * wgt_s[:, None], 0.0)
    out = jnp.zeros((T, d), jnp.float32).at[tid_s].add(contrib)
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]
                      ).astype(jnp.float32)
    return out, stats


def moe_apply(p: dict, x: jnp.ndarray, cfg, layer=None) -> tuple:
    """MoE FFN over x (B, S, d) → (out, aux_loss, stats), stats (2,) f32:
    the routed (token, expert) assignments computed and the experts that
    got at least one (summed over expert shards).  ``layer``: the index
    of this layer's experts when ``p`` holds every layer's stacks
    (``EXPERT_STACKS``, (L, E, …)); None when they are its own."""
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    idx, w, aux = route(p, x2, cfg)
    stacks = tuple(p[n] for n in EXPERT_STACKS)

    mesh = get_mesh()
    ep = mesh is not None and AXIS_MODEL in mesh.axis_names \
        and cfg.n_experts % mesh.shape[AXIS_MODEL] == 0
    if ep:
        data_axes = tuple(a for a in AXIS_BATCH if a in mesh.axis_names)
        lead = () if layer is None else (None,)
        e_spec = P(*lead, AXIS_MODEL, None, None)

        def shard(x2, idx, w, lyr, *stacks):
            off = jax.lax.axis_index(AXIS_MODEL) * stacks[0].shape[-3]
            out, stats = dispatch_compute(
                x2, idx, w, *stacks, act=cfg.act, offset=off,
                layer=None if layer is None else lyr)
            return (jax.lax.psum(out, AXIS_MODEL),
                    jax.lax.psum(stats, (AXIS_MODEL,) + data_axes))
        out, stats = jax.shard_map(
            shard, mesh=mesh,
            in_specs=(P(data_axes, None), P(data_axes, None),
                      P(data_axes, None), P()) + (e_spec,) * 3,
            out_specs=(P(data_axes, None), P()),
            # the kernel branch is traced here too, and a Pallas call
            # cannot state how its output varies over the mesh
            check_vma=False,
        )(x2, idx, w, jnp.asarray(0 if layer is None else layer, jnp.int32),
          *stacks)
    else:
        out, stats = dispatch_compute(x2, idx, w, *stacks, act=cfg.act,
                                      layer=layer)
    out = out.astype(cfg.cdtype)
    if cfg.n_shared_experts:
        out = out + mlp_apply(p["shared"], x2, cfg.mac, cfg.act, True,
                              cfg.cdtype)
    return out.reshape(B, S, d), aux, stats
