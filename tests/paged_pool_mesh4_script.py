"""Runs under 4 fake CPU devices (subprocess; see test_paged_attention.py).

The lane-dense pools merge the kv heads into their last axis, so they may
split over the model axis only in whole heads.  The reduced qwen1.5-0.5b
has 2 kv heads: at model=4 its pools must stay replicated (a split would
cut heads in half and make every layer all-gather the stacked pool), while
at model=2 they shard.  The model=4 decode step must hold no pool-sized
all-gather, keep its donation, and serve greedy-token-identically to the
single-device gather-path engine.  Each check prints 'OK <name>'.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses

import numpy as np
import jax

from repro.analysis.compiled import _executables, audit_cell
from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.models import init_model
from repro.parallel.sharding import AXIS_MODEL
from repro.serve import Engine

POOL_LEAVES = ("pool_k", "pool_v", "scale_k", "scale_v")


def pool_specs(eng):
    out = {}
    for stage, st in eng.kv.pools.items():
        for name, leaf in st.items():
            if name in POOL_LEAVES:
                out[f"{stage}/{name}"] = tuple(leaf.sharding.spec)
    return out


def main():
    assert jax.device_count() == 4, jax.devices()
    cfg = get_config("qwen1.5-0.5b").reduced()
    assert cfg.n_kv_p == 2, "need 2 kv heads: divides model=2, not model=4"
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 14, 9)]
    mesh4 = make_test_mesh(1, 4)
    mesh2 = make_test_mesh(1, 2)

    def engine(c, mesh):
        return Engine(params, c, n_slots=2, page_size=4, n_pages=64,
                      mesh=mesh, prefill_chunk=8)

    for dt in ("bf16", "int8"):
        c = dataclasses.replace(cfg, kv_cache_dtype=dt)
        specs4 = pool_specs(engine(c, mesh4))
        assert specs4 and all(all(a is None for a in s)
                              for s in specs4.values()), (dt, specs4)
        specs2 = pool_specs(engine(c, mesh2))
        assert specs2 and all(s[-1] == AXIS_MODEL
                              for s in specs2.values()), (dt, specs2)
    print("OK pools_shard_in_whole_heads_only")

    one = {"paged_decode": _executables(cfg, full=False)["paged_decode"]}
    f, cell = audit_cell("qwen1.5-0.5b", cfg, "bf16", mesh4, "model4",
                         exes=one, expected_collectives={})
    assert f == [], [str(x) for x in f]
    rec = cell["executables"]["paged_decode"]
    assert rec["aliases"] >= rec["donated_leaves"] > 0, rec
    print("OK model4_decode_no_pool_gather")

    def serve(mesh, backend):
        eng = engine(dataclasses.replace(cfg, attention_backend=backend),
                     mesh)
        rids = [eng.submit(p, max_new=6) for p in prompts]
        res = eng.run()
        return [res[r].tolist() for r in rids]

    ref = serve(None, "xla")
    out = serve(mesh4, "auto")
    assert out == ref, (out, ref)
    print("OK model4_replicated_pools_token_identical")
    print("ALL_PAGED_POOL_MESH4_OK")


if __name__ == "__main__":
    main()
