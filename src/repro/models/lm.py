"""Model assembly: decoder-only LMs (dense / MoE / MLA / hybrid / xLSTM /
VLM) and the enc-dec dispatch.  Layer stacks are lax.scan'd over stacked
per-layer params (vmapped init) with optional per-layer remat; heterogeneous
stacks (DeepSeek dense-prefix, xLSTM mLSTM/sLSTM groups) are multi-stage.

Public API:
  init_model(key, cfg)                    → params
  apply_model(params, cfg, tokens, …)     → (logits, new_cache, aux)
  init_cache(cfg, batch, max_len)         → decode cache
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.nn.common import (embed_init, embed_apply, norm_init, norm_apply,
                             linear_init, linear, softcap, mm)
from repro.nn import blocks as B
from repro.nn.attention import init_kv_cache
from repro.nn.mla import init_mla_cache
from repro.nn.ssm import init_ssm_cache
from repro.nn.xlstm import init_mlstm_cache, init_slstm_cache
from repro.parallel.sharding import constrain, get_mesh, AXIS_BATCH, AXIS_MODEL
from repro.parallel.statesharding import pool_head_axis


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_init(key, n: int, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _stages(cfg):
    """(name, kind, n_layers) stage list per family."""
    if cfg.family == "moe":
        st = []
        if cfg.first_k_dense:
            st.append(("dense_prefix", "dense", cfg.first_k_dense))
        st.append(("moe_stack", "moe", cfg.n_layers - cfg.first_k_dense))
        return st
    if cfg.family == "hybrid":
        return [("stack", "hybrid", cfg.n_layers)]
    if cfg.family == "xlstm":
        return [("xlstm", "xlstm", cfg.n_layers)]
    return [("stack", "dense", cfg.n_layers)]


def init_model(key, cfg):
    if cfg.mac.executor.requires_prepared_params:
        # serving-only executors (e.g. 'encoded_infer') carry pre-folded
        # tensors derived from calibrated fp params — build them with
        # repro.serve.encoded.prepare_encoded_serving (DESIGN.md §3)
        raise ValueError(
            f"init_model cannot initialize mac mode {cfg.mac.mode!r}; init "
            "in 'fp' mode and transform via "
            "serve.encoded.prepare_encoded_serving")
    if cfg.family == "encdec":
        from .encdec import init_encdec
        return init_encdec(key, cfg)
    ks = jax.random.split(key, 8)
    p = {"embed": embed_init(ks[0], cfg.vocab_p, cfg.d_model, cfg.pdtype)}
    p.update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype, "final_norm"))
    if not cfg.tie_embeddings:
        p["lm_head"] = linear_init(ks[1], cfg.d_model, cfg.vocab_p, "w",
                                   cfg.mac, False, cfg.pdtype)
    if cfg.meta_tokens:
        p["meta"] = (jax.random.normal(ks[2], (cfg.meta_tokens, cfg.d_model),
                                       jnp.float32) * 0.02).astype(cfg.pdtype)
    for i, (name, kind, n) in enumerate(_stages(cfg)):
        kk = jax.random.fold_in(ks[3], i)
        if kind == "xlstm":
            n_s = n // cfg.slstm_every if cfg.slstm_every else 0
            n_m = n - n_s
            p[name] = {"mlstm": _stack_init(
                kk, n_m, lambda k: B.mlstm_block_init(k, cfg))}
            if n_s:
                p[name]["slstm"] = _stack_init(
                    jax.random.fold_in(kk, 1), n_s,
                    lambda k: B.slstm_block_init(k, cfg))
        elif kind == "hybrid":
            p[name] = _stack_init(kk, n,
                                  lambda k: B.hybrid_block_init(k, cfg))
        else:
            ffn = "moe" if kind == "moe" else "dense"
            p[name] = _stack_init(
                kk, n, lambda k: B.decoder_block_init(k, cfg, ffn))
    if cfg.mtp:
        kk = jax.random.split(ks[4], 3)
        p["mtp"] = {"proj": linear_init(kk[0], 2 * cfg.d_model, cfg.d_model,
                                        "w", cfg.mac, False, cfg.pdtype),
                    "block": B.decoder_block_init(kk[1], cfg, "dense")}
        p["mtp"].update(norm_init(cfg.d_model, cfg.norm, cfg.pdtype,
                                  "mtp_norm"))
    return p


# ---------------------------------------------------------------------------
# layer-stack execution
# ---------------------------------------------------------------------------

_CTX_KEYS = ("pos", "pages", "lens", "pad", "layer")  # layer-cache context


def _strip_pos(tree):
    if isinstance(tree, dict):
        return {k: _strip_pos(v) for k, v in tree.items()
                if k not in _CTX_KEYS}
    return tree


def _inject_pos(c_l, kind, ctx):
    """Merge broadcast context (scalar pos, or paged pages/lens) into a
    per-layer cache slice before the block apply."""
    if c_l is None:
        return None
    c_l = dict(c_l)
    if kind == "hybrid":
        c_l["attn"] = dict(c_l["attn"], **ctx)
    else:
        c_l.update(ctx)
    return c_l


def _pin_pools(st, cfg):
    """Constrain a stage's paged pools to cache_specs' placement (whole
    kv heads per model shard, else replicated).  Pinned on the way into
    and out of every layer, so sharding propagation can neither split a
    head inside the layer loop nor hand the donated pools back in
    another layout than they came in (which would drop the donation)."""
    hax = pool_head_axis(cfg.n_kv_p, get_mesh())
    return {n: constrain(a, *([None] * (a.ndim - 1)), hax)
            for n, a in st.items()}


def _scan_stack(params_st, x, cfg, kind: str, windows, cache_st, positions,
                ctx=None):
    """Scan a homogeneous stacked stage. cache_st may be None.

    A paged cache (``ctx`` holds the page table) is the stage's stacked
    pools: they ride the scan carry and each layer writes its own rows in
    place at its index, so a dispatch moves only the pool bytes it
    writes or reads.  Any other cache is sliced per layer (scan xs/ys)."""
    ctx = ctx or {}

    def apply_one(p_l, x, c_l, w_l, layer=None):
        c_l = _inject_pos(c_l, kind, ctx if layer is None
                          else dict(ctx, layer=layer))
        if kind == "hybrid":
            out, c2, a = B.hybrid_block_apply(p_l, x, cfg, window=w_l,
                                              cache=c_l, positions=positions)
        else:
            ffn = "moe" if kind == "moe" else "dense"
            out, c2, a = B.decoder_block_apply(p_l, x, cfg, ffn=ffn,
                                               window=w_l, cache=c_l,
                                               positions=positions)
        return out, _strip_pos(c2) if c2 is not None else None, a

    fn = jax.checkpoint(apply_one) if cfg.remat else apply_one
    paged = cache_st is not None and "pages" in ctx
    L = windows.shape[0]
    zero = jnp.zeros((), jnp.float32)

    if not cfg.scan_layers:       # cost probes: unrolled layer loop
        aux = zero
        cs = []
        for i in range(L):
            p_l = jax.tree_util.tree_map(lambda a: a[i], params_st)
            if paged:
                x, cache_st, a = fn(p_l, x, _pin_pools(cache_st, cfg),
                                    windows[i], i)
                cache_st = _pin_pools(cache_st, cfg)
            else:
                c_l = None if cache_st is None else \
                    jax.tree_util.tree_map(lambda a: a[i], cache_st)
                x, c2, a = fn(p_l, x, c_l, windows[i])
                cs.append(c2)
            aux = aux + a
        if cache_st is None or paged:
            return x, cache_st, aux
        return x, jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls, 0), *cs), aux

    if cache_st is None:
        def body(carry, xs):
            x, aux = carry
            p_l, w_l = xs
            x, _, a = fn(p_l, x, None, w_l)
            return (x, aux + a), None
        (x, aux), _ = jax.lax.scan(body, (x, zero), (params_st, windows))
        return x, None, aux

    if paged:
        def body(carry, xs):
            x, aux, c = carry
            p_l, w_l, i = xs
            x, c, a = fn(p_l, x, _pin_pools(c, cfg), w_l, i)
            return (x, aux + a, _pin_pools(c, cfg)), None
        (x, aux, cache_st), _ = jax.lax.scan(
            body, (x, zero, cache_st),
            (params_st, windows, jnp.arange(L, dtype=jnp.int32)))
        return x, cache_st, aux

    def body(carry, xs):
        x, aux = carry
        p_l, w_l, c_l = xs
        x, c2, a = fn(p_l, x, c_l, w_l)
        return (x, aux + a), c2
    (x, aux), new_cache = jax.lax.scan(
        body, (x, zero), (params_st, windows, cache_st))
    return x, new_cache, aux


def _scan_xlstm(params_st, x, cfg, cache_st):
    """xLSTM stage: groups of (slstm_every−1) mLSTM + 1 sLSTM (or pure m)."""
    zero = jnp.zeros((), jnp.float32)

    def m_apply(p_l, x, c_l):
        return B.mlstm_block_apply(p_l, x, cfg, cache=c_l)

    def s_apply(p_l, x, c_l):
        return B.slstm_block_apply(p_l, x, cfg, cache=c_l)

    mfn = jax.checkpoint(m_apply) if cfg.remat else m_apply
    sfn = jax.checkpoint(s_apply) if cfg.remat else s_apply

    m_params = params_st["mlstm"]
    n_m = jax.tree_util.tree_leaves(m_params)[0].shape[0]
    mc = None if cache_st is None else cache_st["mlstm"]

    def m_body(carry, xs):
        x = carry
        p_l, c_l = xs
        x, c2, _ = mfn(p_l, x, c_l)
        return x, c2

    if not cfg.scan_layers:       # cost probes: unrolled (m…m s)* pattern
        per = cfg.slstm_every or (n_m + 1)
        s_params = params_st.get("slstm")
        mi = si = 0
        mcs, scs = [], []
        total = n_m + (jax.tree_util.tree_leaves(s_params)[0].shape[0]
                       if s_params is not None else 0)
        for li in range(total):
            is_s = cfg.slstm_every and (li % per == per - 1) \
                and s_params is not None
            if is_s:
                p_l = jax.tree_util.tree_map(lambda a: a[si], s_params)
                c_l = None if cache_st is None else jax.tree_util.tree_map(
                    lambda a: a[si], cache_st["slstm"])
                x, c2, _ = sfn(p_l, x, c_l)
                scs.append(c2)
                si += 1
            else:
                p_l = jax.tree_util.tree_map(lambda a: a[mi], m_params)
                c_l = None if cache_st is None else jax.tree_util.tree_map(
                    lambda a: a[mi], mc)
                x, c2, _ = mfn(p_l, x, c_l)
                mcs.append(c2)
                mi += 1
        if cache_st is None:
            return x, None, zero
        out = {"mlstm": jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls, 0), *mcs)}
        if scs:
            out["slstm"] = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls, 0), *scs)
        return x, out, zero

    if cfg.slstm_every and "slstm" in params_st:
        s_params = params_st["slstm"]
        n_s = jax.tree_util.tree_leaves(s_params)[0].shape[0]
        per = n_m // n_s
        mp = jax.tree_util.tree_map(
            lambda a: a.reshape(n_s, per, *a.shape[1:]), m_params)
        mcg = None if mc is None else jax.tree_util.tree_map(
            lambda a: a.reshape(n_s, per, *a.shape[1:]), mc)
        sc = None if cache_st is None else cache_st["slstm"]

        def g_body(carry, xs):
            x = carry
            mp_g, sp_g, mc_g, sc_g = xs
            if mc_g is None:
                x, _ = jax.lax.scan(
                    lambda xx, pp: (m_body(xx, (pp, None))[0], None),
                    x, mp_g)
                mc2 = None
            else:
                x, mc2 = jax.lax.scan(m_body, x, (mp_g, mc_g))
            x, sc2, _ = sfn(sp_g, x, sc_g)
            return x, (mc2, sc2)

        if cache_st is None:
            def g_nb(x, xs):
                mp_g, sp_g = xs
                x, _ = g_body(x, (mp_g, sp_g, None, None))
                return x, None
            x, _ = jax.lax.scan(g_nb, x, (mp, s_params))
            return x, None, zero
        x, (mc2, sc2) = jax.lax.scan(
            lambda xx, xs: g_body(xx, xs), x, (mp, s_params, mcg, sc))
        mc2 = jax.tree_util.tree_map(
            lambda a: a.reshape(n_m, *a.shape[2:]), mc2)
        return x, {"mlstm": mc2, "slstm": sc2}, zero

    if cache_st is None:
        x, _ = jax.lax.scan(lambda xx, pp: (m_body(xx, (pp, None))[0], None),
                            x, m_params)
        return x, None, zero
    x, mc2 = jax.lax.scan(m_body, x, (m_params, mc))
    return x, {"mlstm": mc2}, zero


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def apply_model(params, cfg, tokens, *, img=None, enc_x=None, cache=None,
                return_hidden: bool = False):
    """tokens (B, S) int32 → (logits (B, S', vocab_p), new_cache, aux).

    img: (B, n_patches, d) VLM patch embeddings (replace leading positions).
    enc_x: encoder frame embeddings for enc-dec models.
    cache: decode/prefill cache (None for training).
    """
    if cfg.family == "encdec":
        from .encdec import apply_encdec
        return apply_encdec(params, cfg, tokens, enc_x=enc_x, cache=cache,
                            return_hidden=return_hidden)
    B_, S = tokens.shape
    x = embed_apply(params["embed"], tokens, cfg.cdtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), cfg.cdtype)
    paged = cache is not None and "pages" in cache
    if paged:
        pos0 = cache["lens"]                  # (B,) ragged per-slot offsets
    else:
        pos0 = jnp.zeros((), jnp.int32) if cache is None else cache["pos"]
    if img is not None and cfg.n_patches:
        np_eff = min(cfg.n_patches, S)     # patches lead the prompt
        x = jax.lax.dynamic_update_slice(
            x, img[:, :np_eff].astype(x.dtype), (0, 0, 0))
    # meta tokens lead the sequence: prepended for training and for the
    # prefill pass (cache present, S>1 ⇒ prompt ingestion from position 0);
    # decode steps (S==1) find them already in the cache.
    if cfg.meta_tokens and (cache is None or S > 1):
        meta = jnp.broadcast_to(params["meta"].astype(x.dtype)[None],
                                (B_, cfg.meta_tokens, cfg.d_model))
        x = jnp.concatenate([meta, x], axis=1)
        S = S + cfg.meta_tokens
    x = constrain(x, AXIS_BATCH, None, None)
    pad = cache.get("pad") if (cache is not None and not paged) else None
    if paged:
        positions = pos0[:, None] + jnp.arange(S)[None, :]     # (B, S)
        ctx = {"pages": cache["pages"], "lens": cache["lens"]}
    elif pad is not None:
        # left-padded ragged batch: row b's tokens start at pad[b] pad
        # slots, so its logical positions are slot - pad[b] (negative for
        # the pads themselves — those keys are masked in attention)
        positions = pos0 + jnp.arange(S)[None, :] - pad[:, None]
        ctx = {"pos": pos0, "pad": pad}
    else:
        positions = pos0 + jnp.arange(S)
        ctx = {"pos": pos0}

    aux = jnp.zeros((), jnp.float32)
    new_layers = {}
    windows_all = np.asarray(
        [w if w is not None else -1 for w in cfg.layer_windows], np.int32)
    off = 0
    for name, kind, n in _stages(cfg):
        win = jnp.asarray(windows_all[off:off + n])
        c_st = None if cache is None else cache["layers"][name]
        if kind == "xlstm":
            x, c2, a = _scan_xlstm(params[name], x, cfg, c_st)
        else:
            x, c2, a = _scan_stack(params[name], x, cfg, kind, win, c_st,
                                   positions, ctx=ctx)
        aux = aux + a
        if c2 is not None:
            new_layers[name] = c2
        off += n

    h = norm_apply(params, x, cfg.norm, cfg.norm_eps, "final_norm")
    logits = _head(params, cfg, h)
    new_cache = None
    if paged:
        new_cache = {"layers": new_layers, "pages": cache["pages"],
                     "lens": cache["lens"] + S}
    elif cache is not None:
        new_cache = {"pos": pos0 + S, "layers": new_layers}
        if pad is not None:
            new_cache["pad"] = pad
    if return_hidden:
        return logits, new_cache, aux, h
    return logits, new_cache, aux


def _head(params, cfg, h):
    # tied heads read the embedding table and stay fp in every MAC mode; an
    # untied lm_head is a normal 'w' linear, so under 'encoded_infer' it
    # routes through the folded encoded matmul like any other projection
    if cfg.tie_embeddings:
        logits = mm(h, params["embed"]["table"].T, cfg.cdtype)
    else:
        logits = linear(params["lm_head"], "w", h, cfg.mac, cfg.cdtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    logits = logits.astype(cfg.cdtype)    # keep (B,S,V) temps compact
    return constrain(logits, AXIS_BATCH, None, AXIS_MODEL)


def mtp_logits(params, cfg, h, tokens):
    """DeepSeek-style Multi-Token-Prediction head: predicts token t+2 from
    (h_t, emb(t+1)).  Returns logits (B, S-1, vocab_p)."""
    e = embed_apply(params["embed"], tokens[:, 1:], cfg.cdtype)
    hin = jnp.concatenate([h[:, :-1], e], axis=-1)
    x = linear(params["mtp"]["proj"], "w", hin, cfg.mac, cfg.cdtype)
    x, _, _ = B.decoder_block_apply(params["mtp"]["block"], x, cfg,
                                    ffn="dense", window=None)
    x = norm_apply(params["mtp"], x, cfg.norm, cfg.norm_eps, "mtp_norm")
    return _head(params, cfg, x)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int):
    if cfg.family == "encdec":
        from .encdec import init_encdec_cache
        return init_encdec_cache(cfg, batch, max_len)
    max_len = max_len + cfg.meta_tokens
    layers = {}
    for name, kind, n in _stages(cfg):
        if kind == "xlstm":
            n_s = n // cfg.slstm_every if cfg.slstm_every else 0
            layers[name] = {"mlstm": init_mlstm_cache(cfg, batch, n - n_s)}
            if n_s:
                layers[name]["slstm"] = init_slstm_cache(cfg, batch, n_s)
            for sub in layers[name].values():
                sub.pop("pos", None)
        elif kind == "hybrid":
            att = init_kv_cache(cfg, batch, max_len, n)
            att.pop("pos")
            ssm = init_ssm_cache(cfg, batch, n)
            layers[name] = {"attn": att, "ssm": ssm}
        elif cfg.use_mla:
            c = init_mla_cache(cfg, batch, max_len, n)
            c.pop("pos")
            layers[name] = c
        else:
            c = init_kv_cache(cfg, batch, max_len, n)
            c.pop("pos")
            layers[name] = c
    return {"pos": jnp.zeros((), jnp.int32), "layers": layers}


def supports_paged_cache(cfg) -> bool:
    """Block paging needs a plain per-layer (k, v) cache: dense/moe GQA
    attention without MLA latents, recurrent state, or meta tokens."""
    return (cfg.family in ("dense", "moe") and not cfg.use_mla
            and not cfg.meta_tokens)


def init_paged_cache(cfg, n_pages: int, page_size: int):
    """Block-paged serving cache: per attention stage a shared pool of
    fixed-size pages, lane-dense ``pool_k/pool_v (L, n_pages, page_size,
    n_kv·hd)``: on a TPU a page is a ``(page_size, n_kv·hd)`` tile in the
    default layout, which is the paged kernel's block, so no dispatch
    relayouts a pool (DESIGN.md §8).

    Sequences address the pool through (pages, lens) passed alongside the
    cache at apply time (see repro.nn.paged); page 0 is the scratch page.
    Allocation lives host-side in repro.serve.paged_cache.

    With ``cfg.kv_cache_dtype`` 'int8'/'int4' the pools store quantized
    pages (int4 packs two head dims per byte) plus f32 per-token
    per-kv-head ``scale_k/scale_v (L, n_pages, n_kv·page_size)`` side
    pools (DESIGN.md §11)."""
    if not supports_paged_cache(cfg):
        raise ValueError(
            f"paged KV cache unsupported for arch {cfg.arch!r} "
            f"(family={cfg.family}, mla={cfg.use_mla}, "
            f"meta_tokens={cfg.meta_tokens}); use the dense init_cache")
    from repro.quant.kvcache import kv_pool_layout, scale_pool_shape
    pdt, phd, quant = kv_pool_layout(cfg)
    layers = {}
    for name, kind, n in _stages(cfg):
        shape = (n, n_pages, page_size, cfg.n_kv_p * phd)
        st = {"pool_k": jnp.zeros(shape, pdt),
              "pool_v": jnp.zeros(shape, pdt)}
        if quant:
            # per-token per-kv-head scales (DESIGN.md §11), one lane-dense
            # row per page; page axis at position 1 like the pools so
            # copy_page COW carries them
            sshape = scale_pool_shape(n, n_pages, page_size, cfg.n_kv_p)
            st["scale_k"] = jnp.zeros(sshape, jnp.float32)
            st["scale_v"] = jnp.zeros(sshape, jnp.float32)
        layers[name] = st
    return {"layers": layers}
