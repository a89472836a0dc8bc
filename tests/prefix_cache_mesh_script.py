"""Runs under 2 fake CPU devices (subprocess; see test_prefix_cache.py).

Prefix caching + chunked prefill must compose with tensor-parallel
serving: a model=2 mesh engine with the prefix cache enabled serves a
shared-prefix workload greedy-token-identically to the single-device
cache-disabled engine, and still reports prefix hits.  Each check prints
'OK <name>'.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import jax

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.models import init_model
from repro.serve import Engine


def main():
    assert jax.device_count() == 2, jax.devices()
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(
        0, cfg.vocab_size, n).astype(np.int32)]) for n in (3, 5, 4)]

    def serve(mesh, prefix_cache):
        eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=64,
                     mesh=mesh, prefix_cache=prefix_cache, prefill_chunk=8)
        rids = [eng.submit(p, max_new=6) for p in prompts]
        res = eng.run()
        return [res[r].tolist() for r in rids], eng.stats()

    ref, _ = serve(None, False)
    mesh = make_test_mesh(1, 2)
    out, st = serve(mesh, True)
    assert out == ref, (out, ref)
    print("OK prefix_mesh_token_identical")
    assert st["prefix_hit_tokens"] > 0, st
    print("OK prefix_mesh_nonzero_hit_rate")
    print("ALL_PREFIX_MESH_OK")


if __name__ == "__main__":
    main()
