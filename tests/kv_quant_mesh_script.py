"""Runs under 2 fake CPU devices (subprocess; see test_kv_quant.py).

Quantized paged pools compose with tensor-parallel serving: the int8/int4
value pools shard over kv heads (axis 3) and the f32 scale rows shard over
the matching kv-head axis (statesharding._CACHE_RULES, DESIGN.md §11), and
the fused kernel dequantizes shard-locally inside shard_map.  A model=2
mesh engine must serve greedy-token-identically to the single-device
engine *with the same kv-dtype* (quantize-on-scatter is deterministic, so
sharding cannot change the stored bytes).  Each check prints 'OK <name>'.
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

    def serve(mesh, backend, kv_dtype):
        c = dataclasses.replace(cfg, attention_backend=backend,
                                kv_cache_dtype=kv_dtype)
        eng = Engine(params, c, n_slots=2, page_size=4, n_pages=64,
                     mesh=mesh, prefill_chunk=8)
        rids = [eng.submit(p, max_new=6) for p in prompts]
        res = eng.run()
        return [res[r].tolist() for r in rids]

    mesh = make_test_mesh(1, 2)
    for kv_dtype in ("int8", "int4"):
        ref = serve(None, "xla", kv_dtype)
        out = serve(mesh, "pallas_interpret", kv_dtype)
        assert out == ref, (kv_dtype, out, ref)
        print(f"OK kv_quant_mesh_{kv_dtype}_token_identical")
        out_b = serve(mesh, "blocked", kv_dtype)
        assert out_b == ref, (kv_dtype, out_b, ref)
        print(f"OK kv_quant_mesh_{kv_dtype}_blocked_token_identical")
    print("ALL_KV_QUANT_MESH_OK")


if __name__ == "__main__":
    main()
