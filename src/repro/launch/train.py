"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --steps 200 --batch 32 --seq 256 --ckpt-dir /tmp/ckpt [--reduced]

Wires together: config registry → mesh (when >1 device) → sharded train
state → data pipeline (per-host, deterministic, straggler skip) → train loop
with async checkpointing, emergency save on SIGTERM, and resume.

Fault-tolerance posture at scale (documented here because the CPU container
can't kill real hosts):
  * restart-based recovery: any crash → all hosts restart, restore the
    latest committed checkpoint (atomic rename protocol), replay the data
    stream deterministically from (seed, step, host);
  * elastic rescale: checkpoints are mesh-agnostic (tests cover 8→4);
  * stragglers: prefetch + skip-batch watchdog in DataPipeline; at scale,
    the same step-keyed determinism lets backup hosts recompute a shard;
  * async checkpoint thread overlaps the save with compute;
  * XLA latency-hiding flags for comm/compute overlap are set below.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import signal
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--mac-mode", default="fp",
                    choices=["fp", "int8", "encoded"])
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="fake host devices (0 = real)")
    ap.add_argument("--debug-nan", action="store_true",
                    help="raise on the first NaN any dispatch produces "
                         "(debug-only: forces per-op sync)")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    from repro.launch.env import set_debug_nan, use_compile_cache
    use_compile_cache()
    if args.debug_nan:
        set_debug_nan(True)
    # comm/compute overlap (latency-hiding scheduler) — harmless on CPU
    os.environ.setdefault(
        "LIBTPU_INIT_ARGS",
        "--xla_tpu_enable_latency_hiding_scheduler=true")

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.layers import MacConfig
    from repro.core.mac import EncodedMac
    from repro.train import make_train_step, init_train_state
    from repro.data.synthetic import SyntheticLMDataset
    from repro.data.pipeline import DataPipeline
    from repro.ckpt import (save_checkpoint, restore_checkpoint,
                            async_save_checkpoint, latest_step)
    from repro.launch.mesh import make_test_mesh
    from repro.parallel.sharding import set_mesh, param_specs, batch_spec
    from repro.parallel.statesharding import opt_state_specs

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.mac_mode != "fp":
        mac = EncodedMac.default() if args.mac_mode == "encoded" else None
        cfg = dataclasses.replace(cfg, mac=MacConfig(mode=args.mac_mode,
                                                     mac=mac))
    if args.microbatch:
        cfg = dataclasses.replace(cfg, microbatch=args.microbatch)

    n_dev = jax.device_count()
    mesh = None
    if n_dev > 1:
        import numpy as np
        model_ax = 1
        for m in (16, 8, 4, 2):
            if n_dev % m == 0 and cfg.d_ff % m == 0:
                model_ax = m
                break
        mesh = make_test_mesh(n_dev // model_ax, model_ax)
        print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    ds = SyntheticLMDataset(cfg.vocab_size, args.seq, seed=17)
    pipe = DataPipeline(lambda s: ds.batch(s, args.batch), prefetch=2,
                        skip_threshold=30.0)

    with set_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(0), cfg,
                                 grad_compress=args.grad_compress)
        st_sh = None
        if mesh is not None:
            p_sh = param_specs(state["params"], mesh, fsdp=cfg.fsdp)
            st_sh = opt_state_specs(jax.eval_shape(lambda: state), p_sh,
                                    mesh)
            state = jax.device_put(state, st_sh)
        step_fn = jax.jit(make_train_step(cfg, total_steps=args.steps,
                                          grad_compress=args.grad_compress),
                          out_shardings=(st_sh, None)
                          if st_sh is not None else None,
                          donate_argnums=(0,))

        start = latest_step(args.ckpt_dir)
        if start is not None:
            print(f"resuming from step {start}")
            state = restore_checkpoint(args.ckpt_dir, start, state, st_sh)
        start = start or 0

        stop = {"now": False}
        signal.signal(signal.SIGTERM,
                      lambda *_: stop.update(now=True))

        ckpt_thread = None
        t0 = time.time()
        for i in range(start, args.steps):
            sid, b = pipe.next()
            if mesh is not None:
                b = {k: jax.device_put(jnp.asarray(v),
                                       batch_spec(mesh, v.ndim))
                     for k, v in b.items()}
            else:
                b = {k: jnp.asarray(v) for k, v in b.items()}
            state, m = step_fn(state, b)
            if i % 10 == 0 or i == args.steps - 1:
                toks = args.batch * args.seq * (i - start + 1)
                print(f"step {i} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['gnorm']):.2f} "
                      f"tok/s {toks / (time.time() - t0):,.0f}", flush=True)
            if (i + 1) % args.ckpt_every == 0 or stop["now"]:
                if ckpt_thread is not None:
                    ckpt_thread.join()
                ckpt_thread = async_save_checkpoint(args.ckpt_dir, i + 1,
                                                    jax.device_get(state))
                if stop["now"]:
                    print("emergency checkpoint committed; exiting")
                    break
        if ckpt_thread is not None:
            ckpt_thread.join()
        if pipe.skipped:
            print(f"straggler-skipped steps: {pipe.skipped}")
    pipe.stop()
    print("done")


if __name__ == "__main__":
    main()
