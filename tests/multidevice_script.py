"""Runs under 8 fake CPU devices (subprocess; see test_multidevice.py).
Each check prints 'OK <name>' — the wrapper asserts all are present."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import dataclasses
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.models import init_model, apply_model, init_cache
from repro.train import make_train_step, init_train_state
from repro.data.synthetic import SyntheticLMDataset
from repro.parallel.sharding import set_mesh, param_specs, batch_spec
from repro.launch.mesh import make_mesh, make_test_mesh
from repro.parallel.statesharding import opt_state_specs, cache_specs

assert jax.device_count() == 8, jax.device_count()


def tiny_cfg(**kw):
    cfg = get_config("qwen1.5-0.5b").reduced()
    return dataclasses.replace(cfg, n_layers=2, d_model=64, head_dim=16,
                               n_heads=4, n_kv_heads=4, d_ff=128,
                               vocab_size=128, **kw)


def tree_allclose(a, b, rtol=2e-3, atol=2e-3):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


# --------------------------------------------------------------- DP×TP == 1dev
cfg = tiny_cfg()
ds = SyntheticLMDataset(cfg.vocab_size, 16, seed=0)
batch = {k: jnp.asarray(v) for k, v in ds.batch(0, 8).items()}

state0 = init_train_state(jax.random.PRNGKey(0), cfg)
step_plain = jax.jit(make_train_step(cfg))
s_ref = state0
for i in range(2):
    s_ref, m_ref = step_plain(s_ref, batch)

mesh = make_test_mesh(4, 2)
with set_mesh(mesh):
    p_sh = param_specs(state0["params"], mesh)
    st_sh = opt_state_specs(jax.eval_shape(lambda: state0), p_sh, mesh)
    st_dev = jax.device_put(state0, st_sh)
    b_dev = {k: jax.device_put(v, batch_spec(mesh, v.ndim))
             for k, v in batch.items()}
    step_sh = jax.jit(make_train_step(cfg), out_shardings=(st_sh, None))
    s_d = st_dev
    for i in range(2):
        s_d, m_d = step_sh(s_d, b_dev)
tree_allclose(s_ref["params"], jax.device_get(s_d["params"]))
assert abs(float(m_ref["loss"]) - float(m_d["loss"])) < 1e-3
print("OK dp_tp_matches_single")

# ---------------------------------------------------------------- EP shard_map
cfg_moe = dataclasses.replace(
    get_config("qwen3-moe-235b-a22b").reduced(), n_layers=2, d_model=64,
    head_dim=16, n_heads=4, n_kv_heads=2, d_ff_expert=32, vocab_size=128,
    n_experts=8, top_k=2)
params_moe = init_model(jax.random.PRNGKey(1), cfg_moe)
toks = jnp.asarray(np.random.default_rng(0).integers(
    0, cfg_moe.vocab_size, (4, 8)), jnp.int32)
ref_logits, _, _ = apply_model(params_moe, cfg_moe, toks)   # no mesh: dense
with set_mesh(mesh):
    p_sh = param_specs(params_moe, mesh)
    p_dev = jax.device_put(params_moe, p_sh)
    t_dev = jax.device_put(toks, batch_spec(mesh, 2))
    ep_logits, _, _ = jax.jit(
        lambda p, t: apply_model(p, cfg_moe, t))(p_dev, t_dev)
tree_allclose(ref_logits, jax.device_get(ep_logits), rtol=5e-3, atol=5e-3)
print("OK moe_ep_matches_dense")

# ------------------------------------------------------------- split-KV decode
cfg_d = tiny_cfg()
params_d = init_model(jax.random.PRNGKey(2), cfg_d)
cache = init_cache(cfg_d, 4, 16)
prompt = jnp.asarray(np.random.default_rng(1).integers(
    0, cfg_d.vocab_size, (4, 8)), jnp.int32)
lg_ref, cache_ref, _ = apply_model(params_d, cfg_d, prompt, cache=cache)
with set_mesh(mesh):
    c_sh = cache_specs(jax.eval_shape(lambda: cache), mesh)
    c_dev = jax.device_put(cache, c_sh)
    p_sh = param_specs(params_d, mesh)
    p_dev = jax.device_put(params_d, p_sh)
    lg_s, cache_s, _ = jax.jit(
        lambda p, c, t: apply_model(p, cfg_d, t, cache=c))(
            p_dev, c_dev, jax.device_put(prompt, batch_spec(mesh, 2)))
    nxt = jnp.argmax(lg_s[:, -1:], -1).astype(jnp.int32)
    lg2_s, _, _ = jax.jit(
        lambda p, c, t: apply_model(p, cfg_d, t, cache=c))(
            p_dev, cache_s, nxt)
nxt_ref = jnp.argmax(lg_ref[:, -1:], -1).astype(jnp.int32)
lg2_ref, _, _ = apply_model(params_d, cfg_d, nxt_ref, cache=cache_ref)
np.testing.assert_array_equal(np.asarray(nxt), np.asarray(nxt_ref))
tree_allclose(lg2_ref, jax.device_get(lg2_s), rtol=5e-3, atol=5e-3)
print("OK splitkv_decode_matches")

# ------------------------------------------------------- compressed allreduce
state_c = init_train_state(jax.random.PRNGKey(0), cfg, grad_compress=True)
with set_mesh(mesh):
    p_sh = param_specs(state_c["params"], mesh)
    st_sh = opt_state_specs(jax.eval_shape(lambda: state_c), p_sh, mesh)
    st_dev = jax.device_put(state_c, st_sh)
    step_c = jax.jit(make_train_step(cfg, grad_compress=True),
                     out_shardings=(st_sh, None))
    s_c, m_c = step_c(st_dev, b_dev)
# one step with int8-EF compression stays close to the uncompressed step
s_u, m_u = step_plain(state0, batch)
err = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
       for a, b in zip(jax.tree_util.tree_leaves(s_u["params"]),
                       jax.tree_util.tree_leaves(
                           jax.device_get(s_c["params"])))]
assert max(err) < 5e-3, max(err)
assert "err" in s_c and any(float(jnp.abs(l).max()) > 0
                            for l in jax.tree_util.tree_leaves(s_c["err"]))
print("OK compressed_allreduce")

# ------------------------------------------------------------------- pipeline
from repro.parallel.pipeline import pipeline_apply
S_stages, M, mb, dd = 4, 8, 2, 16
mesh_p = make_mesh((4,), ("stage",))
rng = np.random.default_rng(3)
Ws = jnp.asarray(rng.normal(size=(S_stages, dd, dd)) / np.sqrt(dd),
                 jnp.float32)
x = jnp.asarray(rng.normal(size=(M, mb, dd)), jnp.float32)

def stage_fn(w, h):
    return jnp.tanh(h @ w)

out_pp = pipeline_apply(stage_fn, mesh_p, "stage", Ws, x)
ref = x
for sidx in range(S_stages):
    ref = jnp.tanh(ref @ Ws[sidx])
np.testing.assert_allclose(np.asarray(out_pp), np.asarray(ref),
                           rtol=1e-5, atol=1e-5)
print("OK pipeline_parallel")

# ------------------------------------------------------------- elastic rescale
import tempfile
from repro.ckpt import save_checkpoint, restore_checkpoint
with tempfile.TemporaryDirectory() as td:
    save_checkpoint(td, 0, jax.device_get(s_d))         # from mesh (4,2)
    mesh2 = make_test_mesh(2, 4)                        # new topology
    with set_mesh(mesh2):
        p_sh2 = param_specs(state0["params"], mesh2)
        st_sh2 = opt_state_specs(jax.eval_shape(lambda: state0), p_sh2,
                                 mesh2)
        restored = restore_checkpoint(td, 0, state0, shardings=st_sh2)
        b2 = {k: jax.device_put(v, batch_spec(mesh2, v.ndim))
              for k, v in batch.items()}
        step2 = jax.jit(make_train_step(cfg), out_shardings=(st_sh2, None))
        s2, m2 = step2(restored, b2)
    # reference: continue on the original layout
    s3, m3 = step_plain(jax.device_get(s_d), batch)
    tree_allclose(s3["params"], jax.device_get(s2["params"]))
print("OK elastic_rescale")

print("ALL_MULTIDEVICE_OK")
