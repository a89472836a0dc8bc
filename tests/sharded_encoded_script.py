"""Runs under 8 fake CPU devices (subprocess; see test_sharded_encoded.py).

Sharded encoded-MAC serving (DESIGN.md §6): greedy decode through the
continuous-batching engine with calibrated encoded inference on a model=8
mesh must be token-identical to the single-device encoded run, per-device
folded-weight bytes must shrink by the model-axis factor, and the
shard-local Pallas dispatch (column + row roles) must match the unsharded
kernel.  Each check prints 'OK <name>'.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses
import tempfile

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.circuits import Circuit, sample_circuits
from repro.core.encoding import fit_circuit
from repro.core.layers import MacConfig
from repro.core.mac import EncodedMac
from repro.kernels.ops import encoded_matmul
from repro.launch.mesh import make_test_mesh
from repro.models import init_model
from repro.parallel.sharding import set_mesh
from repro.serve import Engine, prepare_encoded_serving

assert jax.device_count() == 8, jax.device_count()

TP = 8
mesh = make_test_mesh(1, TP)

# every sharded projection dim divisible by TP=8: heads*hd = 128, d_ff = 128
cfg = dataclasses.replace(
    get_config("qwen1.5-0.5b").reduced(), n_layers=2, d_model=64,
    head_dim=16, n_heads=8, n_kv_heads=8, d_ff=128, vocab_size=128,
    mac=MacConfig(bits=4))
params = init_model(jax.random.PRNGKey(0), cfg)

tmp = tempfile.mkdtemp()
pe, ce, info = prepare_encoded_serving(
    params, cfg, cache_dir=tmp, m_bits=10, n_samples=8, refine=4,
    calib_batches=2, calib_batch_size=2, calib_seq=16, verbose=False)
assert info["n_folded"] >= 6, info
assert info["roles"]["wq"] == "column" and info["roles"]["wo"] == "row", \
    info["roles"]

rng = np.random.default_rng(3)
prompts = [rng.integers(0, cfg.vocab_size, 6),
           rng.integers(0, cfg.vocab_size, 9),
           rng.integers(0, cfg.vocab_size, 4)]


def decode(mesh):
    eng = Engine(pe, ce, n_slots=2, page_size=8, n_pages=32, mesh=mesh)
    rids = [eng.submit(p, max_new=6) for p in prompts]
    res = eng.run()
    return [res[r].tolist() for r in rids], eng


# ------------------------------------------------- token-identical TP decode
ref_toks, _ = decode(None)
tp_toks, eng = decode(mesh)
assert ref_toks == tp_toks, (ref_toks, tp_toks)
print("OK sharded_encoded_decode_token_identical")

# ----------------------------------------------- per-device fw bytes shrink
glob_bytes = dev_bytes = 0
for path, leaf in jax.tree_util.tree_leaves_with_path(eng.params):
    key = str(path[-1].key) if hasattr(path[-1], "key") else ""
    if not key.endswith("_fw"):
        continue
    glob_bytes += leaf.size * leaf.dtype.itemsize
    local = int(np.prod(leaf.sharding.shard_shape(leaf.shape)))
    dev_bytes += local * leaf.dtype.itemsize
assert glob_bytes > 0
ratio = glob_bytes / dev_bytes
assert ratio > TP * 0.99, (glob_bytes, dev_bytes, ratio)
print(f"OK sharded_encoded_fw_bytes_reduced ratio={ratio:.2f}")

# ------------------------------------- shard-local pallas kernel (col + row)
bits, m_bits, m, k, n = 4, 10, 8, 64, 32
krng = np.random.default_rng(0)
gt, ii = sample_circuits(krng, 1, m_bits, bits, bits)
mac = EncodedMac.from_spec(fit_circuit(Circuit(gt[0], ii[0], bits, bits)))
xc = jnp.asarray(krng.integers(-7, 8, (m, k)), jnp.int8)
wc = jnp.asarray(krng.integers(-7, 8, (k, n)), jnp.int8)
Wt, bias = mac.program.fold_weights(wc, jnp.asarray(mac.spec.s))
mono = mac.program.a_mono_tuples

want = encoded_matmul(xc, Wt, bias, mono, backend="pallas_interpret",
                      bm=8, bn=8, bk=8)
for role in ("column", "row"):
    with set_mesh(mesh):
        got = jax.jit(lambda a: encoded_matmul(
            a, Wt, bias, mono, backend="pallas_interpret",
            bm=8, bn=8, bk=8, role=role))(xc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
print("OK sharded_kernel_roles_match")

print("ALL_SHARDED_ENCODED_OK")
