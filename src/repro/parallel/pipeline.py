"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Stages are laid out on a mesh axis; each device runs ``stage_fn`` on its
layer slice, passing activations to the next stage with ppermute.  With M
microbatches and S stages the schedule runs M+S−1 ticks (bubble fraction
(S−1)/(M+S−1)).  At 512-chip scale this maps the `pod` axis to stages so
only pipeline point-to-points cross the DCI (DESIGN.md §5).

This implementation is forward (inference/serving) and training-loss capable
(grad flows through ppermute); it is exercised on 8 fake devices in tests
and is an optional alternative to the pure DP/TP production mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, mesh: Mesh, axis: str, params_stacked, x_mb):
    """Run x through S pipeline stages.

    stage_fn(stage_params, x) → y, same shape.
    params_stacked: pytree with leading stage axis S (sharded over ``axis``).
    x_mb: (M, mb, …) microbatches (replicated).
    Returns (M, mb, …) outputs.
    """
    S = mesh.shape[axis]

    def body(params_local, x_all):
        # params_local leaves: (1, ...) — this stage's slice
        p = jax.tree_util.tree_map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(axis)
        M = x_all.shape[0]
        n_ticks = M + S - 1
        # carries become stage-varying inside the loop — mark them upfront
        carry_in = jax.lax.pvary(jnp.zeros_like(x_all[0]), (axis,))
        outs = jax.lax.pvary(jnp.zeros_like(x_all), (axis,))

        def tick(t, state):
            carry_in, outs = state
            mb_idx = t - s
            # stage 0 reads the microbatch; others read the permuted carry
            feed = jax.lax.dynamic_index_in_dim(
                x_all, jnp.clip(mb_idx, 0, M - 1), axis=0, keepdims=False)
            x_in = jnp.where(s == 0, feed, carry_in)
            y = stage_fn(p, x_in)
            active = (mb_idx >= 0) & (mb_idx < M)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage writes its output slot (branchless — shard_map VMA)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(mb_idx, 0, M - 1), axis=0)
            outs = jnp.where((s == S - 1) & active, upd, outs)
            # pass to next stage
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, outs)

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (carry_in, outs))
        # collect the last stage's outputs everywhere (cheap psum broadcast)
        my = jnp.where(s == S - 1, 1.0, 0.0)
        outs = jax.lax.psum(outs * my, axis)
        return outs

    pspec = jax.tree_util.tree_map(lambda _: P(axis), params_stacked)
    return jax.shard_map(body, mesh=mesh, in_specs=(pspec, P()),
                         out_specs=P())(params_stacked, x_mb)
