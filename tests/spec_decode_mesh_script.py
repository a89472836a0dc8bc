"""Runs under 2 fake CPU devices (subprocess; see test_spec_decode.py).

Speculative decoding must compose with tensor-parallel serving: a
model=2 mesh engine with ``spec_decode=k`` (draft + k-query verify both
running shard-local over kv-head-sharded pools) serves greedy-token-
identically to the single-device non-speculative engine.  Each check
prints 'OK <name>'.
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

    def serve(mesh, backend, spec):
        c = dataclasses.replace(cfg, attention_backend=backend)
        eng = Engine(params, c, n_slots=2, page_size=4, n_pages=64,
                     mesh=mesh, prefill_chunk=8, spec_decode=spec)
        rids = [eng.submit(p, max_new=8) for p in prompts]
        res = eng.run()
        return [res[r].tolist() for r in rids], eng.stats()

    ref, _ = serve(None, "xla", 0)
    mesh = make_test_mesh(1, 2)
    out, st = serve(mesh, "xla", 4)
    assert out == ref, (out, ref)
    assert st["spec_acceptance_rate"] > 0, st
    print("OK spec_decode_mesh_xla_token_identical")
    out_p, st_p = serve(mesh, "pallas_interpret", 4)
    assert out_p == ref, (out_p, ref)
    assert st_p["spec_acceptance_rate"] > 0, st_p
    print("OK spec_decode_mesh_pallas_token_identical")
    print("ALL_SPEC_DECODE_MESH_OK")


if __name__ == "__main__":
    main()
