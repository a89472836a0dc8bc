"""train_step factory: loss → grad → clip → (optional int8-EF-compressed DP
all-reduce) → optimizer, with microbatch gradient accumulation and remat
handled inside the model (cfg.remat).

The step is pure pjit: gradient reduction across the data axes is implicit in
the sharded loss mean; the explicit shard_map compressed-all-reduce variant
(``grad_compress=True``) trades 8× DP bytes for quantization noise with an
error-feedback buffer in the train state.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import apply_model
from repro.models.lm import mtp_logits
from repro.optim import make_optimizer, warmup_cosine, clip_by_global_norm
from repro.optim.compression import init_error_buffers, ef_compress_tree, \
    decompress_int8
from repro.parallel.sharding import get_mesh, AXIS_BATCH
from jax.sharding import PartitionSpec as P
from .losses import lm_loss

TrainState = dict      # {"params", "opt", "step", ("err")}


def init_train_state(key, cfg, grad_compress: bool = False) -> TrainState:
    from repro.models import init_model
    params = init_model(key, cfg)
    opt = make_optimizer(cfg.optimizer)
    st = {"params": params, "opt": opt.init(params),
          "step": jnp.zeros((), jnp.int32)}
    if grad_compress:
        st["err"] = init_error_buffers(params)
    return st


def _compressed_allreduce(grads, err, mesh):
    """int8 EF all-reduce over the data axes via shard_map (per-shard grads
    arrive already summed over the local batch by autodiff; here we exchange
    the cross-shard sum in int8)."""
    data_axes = tuple(a for a in AXIS_BATCH if a in mesh.axis_names)
    if not data_axes:
        return grads, err

    def f(g, e):
        codes, scales, e2 = ef_compress_tree(g, e)
        summed = jax.tree_util.tree_map(
            lambda c: jax.lax.psum(c.astype(jnp.int32), data_axes), codes)
        n = np.prod([mesh.shape[a] for a in data_axes])
        g2 = jax.tree_util.tree_map(
            lambda s_, c_: decompress_int8(c_, s_) / n, scales, summed)
        return g2, e2

    spec = jax.tree_util.tree_map(lambda _: P(), grads)
    return jax.shard_map(f, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec))(grads, err)


def make_train_step(cfg, *, total_steps: int = 10000, warmup: int = 100,
                    microbatch: Optional[int] = None, clip_norm: float = 1.0,
                    grad_compress: bool = False):
    """Returns train_step(state, batch) → (state, metrics).

    batch: {"tokens" (B,S) int32, "labels" (B,S) int32, + modality extras}.
    ``microbatch``: split the local batch into chunks accumulated with a
    lax.scan (one optimizer step / one gradient exchange per step).
    """
    opt = make_optimizer(cfg.optimizer)
    lr_fn = warmup_cosine(cfg.learning_rate, warmup, total_steps)

    def loss_fn(params, batch):
        extras = {k: batch[k] for k in ("img", "enc_x") if k in batch}
        if cfg.mtp:
            logits, _, aux, h = apply_model(params, cfg, batch["tokens"],
                                            return_hidden=True, **extras)
        else:
            logits, _, aux = apply_model(params, cfg, batch["tokens"],
                                         **extras)
        S = batch["labels"].shape[1]
        loss = lm_loss(logits[:, -S:], batch["labels"])
        if cfg.mtp:
            l2 = mtp_logits(params, cfg, h[:, -S:], batch["tokens"])
            loss = loss + cfg.mtp_weight * lm_loss(l2[:, :-1],
                                                   batch["labels"][:, 2:])
        return loss + cfg.aux_loss_weight * aux, (loss, aux)

    def grads_of(params, batch):
        mb_size = microbatch or (cfg.microbatch or None)
        B = batch["tokens"].shape[0]
        if mb_size is None or mb_size >= B:
            return jax.grad(loss_fn, has_aux=True)(params, batch)
        n = B // mb_size
        mb = jax.tree_util.tree_map(
            lambda a: a.reshape(n, mb_size, *a.shape[1:]), batch)

        def body(acc, b):
            g, aux = jax.grad(loss_fn, has_aux=True)(params, b)
            acc = jax.tree_util.tree_map(
                lambda x, y: x + y.astype(jnp.float32), acc, g)
            return acc, aux

        zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if cfg.unroll_scans:      # cost probes: count every microbatch
            acc, aux = zero, None
            for i in range(n):
                b = jax.tree_util.tree_map(lambda a: a[i], mb)
                acc, aux = body(acc, b)
        else:
            acc, auxs = jax.lax.scan(body, zero, mb)
            aux = jax.tree_util.tree_map(lambda a: a[-1], auxs)
        g = jax.tree_util.tree_map(lambda x: x / n, acc)
        return g, aux

    def train_step(state, batch):
        grads, (loss, aux) = grads_of(state["params"], batch)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        if grad_compress and get_mesh() is not None:
            grads, err = _compressed_allreduce(grads, state["err"],
                                               get_mesh())
            state = dict(state, err=err)
        lr = lr_fn(state["step"])
        new_params, new_opt = opt.update(grads, state["opt"],
                                         state["params"], lr)
        new_state = dict(state, params=new_params, opt=new_opt,
                         step=state["step"] + 1)
        return new_state, {"loss": loss, "aux": aux, "gnorm": gnorm,
                           "lr": lr}

    return train_step
