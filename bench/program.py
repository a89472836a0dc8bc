"""The bridge to the system under test: build the serving engine a cell's
files describe, read what it produced, and tell its compiles apart.
Nothing here measures or judges; ``window`` drives it and ``check``
judges it."""
from __future__ import annotations

import dataclasses
import os
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .spec import ROOT

BUNDLE_DIR = os.path.join(ROOT, ".serving_bundles")   # git-ignored


def model_config(c: dict):
    """The program's ModelConfig from the file's ``program`` block (its
    constructor arguments, as the configuration states them)."""
    from repro.configs.base import ModelConfig
    return ModelConfig(**c["program"])


def exact_circuit_macs(bits: int, families) -> dict:
    """The bit-exact AND-plane product circuit for every named family."""
    from repro.core.circuits import exact_product_circuit
    from repro.core.encoding import EncodingSpec
    from repro.core.mac import EncodedMac
    circ, s = exact_product_circuit(bits, bits)
    mac = EncodedMac.from_spec(EncodingSpec(circ, s, 0.0))
    return {n: mac for n in families}


def serving_params(c: dict, params: dict) -> Tuple[dict, object, dict]:
    """(params, cfg, info) the engine serves.  A configuration with an
    ``encoded`` block goes through the program's calibrate → fold
    pipeline with the exact product circuit; its bundle is kept in the
    checkout's git-ignored ``.serving_bundles`` so a later run with the
    same weights loads it."""
    cfg = model_config(c)
    enc = c.get("encoded")
    if not enc:
        return params, cfg, {}
    if enc["circuit"] != "exact":
        raise ValueError(f"no encoding {enc['circuit']!r}: the benchmark "
                         "serves the exact product circuit only")
    from repro.serve import prepare_encoded_serving
    macs = exact_circuit_macs(int(enc["bits"]), enc["families"])
    return prepare_encoded_serving(
        params, cfg, backend="auto", cache_dir=BUNDLE_DIR,
        seed=int(enc["calib_seed"]), calib_batches=int(enc["calib_batches"]),
        calib_batch_size=int(enc["calib_batch_size"]),
        calib_seq=int(enc["calib_seq"]), macs_override=macs, verbose=False)


def make_engine(params, cfg, engine: dict):
    from repro.serve import Engine
    return Engine(params, cfg, **engine)


@jax.jit
def _gather_pages(pool_k, pool_v, row):
    out = []
    for pool in (pool_k, pool_v):                       # (L, P, ps, H, D)
        g = jnp.take(pool, row, axis=1)
        out.append(g.reshape(g.shape[0], -1, *g.shape[3:]))
    return tuple(out)


def cache_of(engine, req, n_pages: int) -> Tuple[np.ndarray, np.ndarray]:
    """The K and V the paged pools hold in the first ``n_pages`` pages of
    a decoding request's page table, copied to the host so the pools can
    be freed: every stage of the cache (``dense_prefix`` then
    ``moe_stack``, say) in the program's stage order, joined on the layer
    axis, (layers, n_pages * page_size, kv heads, head dim) each; the
    first ``req.n_cached`` rows are its cached tokens.  An architecture
    module whose cache is not paged K and V gives its own ``cache_of``."""
    row = jnp.asarray(engine.kv.ptab[req.slot][:n_pages], jnp.int32)
    ks, vs = [], []
    for st in engine.kv.layers.values():
        k, v = _gather_pages(st["pool_k"], st["pool_v"], row)
        ks.append(np.asarray(k))
        vs.append(np.asarray(v))
    return np.concatenate(ks), np.concatenate(vs)


@dataclasses.dataclass
class CompileCounter:
    """Counts the programs XLA builds or loads from the compile cache
    while it is entered (jitted steps and eager ops alike), from JAX's
    monitoring events."""
    n: int = 0

    def __call__(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self)


def warm_up(engine, engine_kw: dict, seed: int, vocab: int) -> None:
    """Compile (or load) every program the window uses before it opens:
    two requests of one prefill chunk and two new tokens each run the
    prefill chunk, the decode step and the eager page-table slice (one
    program for every slot)."""
    import numpy as np
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    n = int(engine_kw.get("prefill_chunk", 32))
    for _ in range(2):
        engine.submit(rng.integers(0, vocab, n).astype(np.int32), max_new=2)
    engine.run()
