"""Runs under 2 fake CPU devices (subprocess; see test_paged_attention.py).

The fused paged-attention decode path must compose with tensor-parallel
serving: a model=2 mesh engine with the fused backend (``auto`` — the
platform's lowering — and the Pallas kernel in interpret mode, each
shard-local over kv-head-sharded pools via shard_map) serves
greedy-token-identically to the single-device gather-path engine.  Each
check prints 'OK <name>'.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses

import numpy as np
import jax

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.models import init_model
from repro.serve import Engine


def main():
    assert jax.device_count() == 2, jax.devices()
    cfg = get_config("qwen1.5-0.5b").reduced()
    assert cfg.n_kv_p % 2 == 0, "need kv heads divisible by the model axis"
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 14, 9)]

    def serve(mesh, backend):
        c = dataclasses.replace(cfg, attention_backend=backend)
        eng = Engine(params, c, n_slots=2, page_size=4, n_pages=64,
                     mesh=mesh, prefill_chunk=8)
        rids = [eng.submit(p, max_new=6) for p in prompts]
        res = eng.run()
        return [res[r].tolist() for r in rids]

    ref = serve(None, "xla")
    mesh = make_test_mesh(1, 2)
    out = serve(mesh, "auto")
    assert out == ref, (out, ref)
    print("OK paged_attn_mesh_token_identical")
    out_i = serve(mesh, "pallas_interpret")
    assert out_i == ref, (out_i, ref)
    print("OK paged_attn_mesh_interpret_token_identical")
    print("ALL_PAGED_ATTN_MESH_OK")


if __name__ == "__main__":
    main()
