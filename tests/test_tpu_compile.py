"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e at qwen1.5-0.5b widths (d_model 1024, d_ff 2816, 16 heads of 64).

Nothing runs: the TPU compiler that ships with jaxlib compiles against a
described v5e topology, so a kernel that interpret mode accepts but Mosaic
refuses (layouts, shape casts, unsupported dtype casts) fails here on the
CPU.  The topology is described inside a fixture, never at import, so
every test worker collects the same tests and only the one that runs them
loads the TPU library."""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.hlo import entry_param_shapes, input_output_aliases
from repro.kernels.encoded_matmul import encoded_matmul_pallas
from repro.kernels.paged_attention import paged_attn_pallas

# qwen1.5-0.5b (configs/qwen15_05b.py)
D_MODEL, D_FF, N_HEADS, N_KV, HEAD_DIM = 1024, 2816, 16, 16, 64
U = 48                                   # widest encoding: M = 48 planes
MONOS = tuple(tuple(sorted({u % 8, (u // 8 + u) % 8})) for u in range(U))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bm", [8, 128])
@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_encoded_matmul_compiles(one_chip, bm, k, n):
    """The encoded-MAC kernel at a decode (bm=8) and a prefill (bm=128)
    block, for the MLP up (wi/wg) and down (wo) projections."""
    kp = -(-k // 128) * 128
    np_ = -(-n // 128) * 128
    f = jax.jit(lambda x, w, b: encoded_matmul_pallas(
        x, w, b, MONOS, bm=bm, bn=128, bk=128))
    compiled = f.lower(_sds((bm, kp), jnp.int8, one_chip),
                       _sds((U, kp, np_), jnp.bfloat16, one_chip),
                       _sds((np_,), jnp.float32, one_chip)).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("kv", ["float32", "bfloat16", "int8", "int4"])
@pytest.mark.parametrize("sq", [1, 5])
def test_paged_attention_compiles(one_chip, kv, sq):
    """The fused paged-attention kernel over stacked lane-dense pools in
    the compute dtype (f32 as configured), bf16, int8 and packed int4,
    for decode (Sq=1) and a speculative verify step at k=4 (Sq=5): 8
    slots, 16-token pages, 16 table pages, 3 layers."""
    B, ps, P, n_pages, L = 8, 16, 16, 129, 3
    quant = kv in ("int8", "int4")
    f = jax.jit(lambda q, pk, pv, pg, ln, lyr, *sc: paged_attn_pallas(
        q, pk, pv, pg, ln, jnp.int32(2 ** 30), lyr, scale=HEAD_DIM ** -0.5,
        G=N_HEADS // N_KV, scale_k=sc[0] if sc else None,
        scale_v=sc[1] if sc else None))
    dtype, width = ((jnp.uint8, HEAD_DIM // 2) if kv == "int4"
                    else (jnp.dtype(kv), HEAD_DIM))
    pool = _sds((L, n_pages, ps, N_KV * width), dtype, one_chip)
    args = [_sds((B, sq, N_HEADS, HEAD_DIM), jnp.float32, one_chip),
            pool, pool, _sds((B, P), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip)]
    if quant:
        args += [_sds((L, n_pages, N_KV * ps), jnp.float32, one_chip)] * 2
    _assert_mosaic(f.lower(*args).compile())


def test_encoded_matmul_op_name_is_pinned(one_chip):
    """The kernel's device op keeps its name when the function around it
    is renamed: the encoded-MAC roofline finds its ops by
    ``%encoded_matmul_pallas.<n>``."""
    kernel = encoded_matmul_pallas.__wrapped__

    def renamed_outer(x, w, b):
        return kernel(x, w, b, MONOS, bm=8, bn=128, bk=128)
    compiled = jax.jit(renamed_outer).lower(
        _sds((8, D_MODEL), jnp.int8, one_chip),
        _sds((U, D_MODEL, D_MODEL), jnp.bfloat16, one_chip),
        _sds((D_MODEL,), jnp.float32, one_chip)).compile()
    _assert_mosaic(compiled)
    assert "%encoded_matmul_pallas." in compiled.as_text()


def test_paged_attention_op_name_is_pinned(one_chip):
    """As above for the fused paged-attention kernel, whose roofline
    finds its ops by ``%paged_attn_pallas.<n>``."""
    kernel = paged_attn_pallas.__wrapped__

    def renamed_outer(q, pk, pv, pg, ln):
        return kernel(q, pk, pv, pg, ln, jnp.int32(2 ** 30), jnp.int32(0),
                      scale=HEAD_DIM ** -0.5, G=N_HEADS // N_KV)
    pool = _sds((2, 129, 16, N_KV * HEAD_DIM), jnp.float32, one_chip)
    compiled = jax.jit(renamed_outer).lower(
        _sds((8, 1, N_HEADS, HEAD_DIM), jnp.float32, one_chip), pool, pool,
        _sds((8, 16), jnp.int32, one_chip),
        _sds((8,), jnp.int32, one_chip)).compile()
    _assert_mosaic(compiled)
    assert "%paged_attn_pallas." in compiled.as_text()


# ---------------------------------------------------------------------------
# the serving steps move no whole KV pool
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                    r"([\w\-]+)\((.*)$")


def _pool_moves(hlo: str, leaf, staging_ok: bool = False):
    """Instructions, fused ones included, that copy, slice or allocate a
    whole layer of the stacked pool ``leaf`` (L, n_pages, …), or all of
    it, in any shape: ``[1 or L, n_pages, …]`` with a page's elements
    after the page axis.  ``staging_ok`` lets through the asynchronous
    copies (``copy-start``/``copy-done``) by which the compiler stages a
    buffer in on-chip memory and back, in its own layout."""
    L, n_pages = leaf.shape[:2]
    page = math.prod(leaf.shape[2:])
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dims, op, rest = m.groups()
        if staging_ok and op in ("copy-start", "copy-done"):
            continue
        if not (op.startswith(("copy", "dynamic-slice",
                               "dynamic-update-slice"))
                or 'custom_call_target="AllocateBuffer"' in rest):
            continue
        d = [int(x) for x in dims.split(",") if x]
        for i, n in enumerate(d):
            if n == n_pages and math.prod(d[:i]) in (1, L) \
                    and math.prod(d[i + 1:]) == page:
                out.append(f"{op} %{name}[{dims}]")
                break
    return out


def _qwen_cfg(n_layers, n_kv, dtype, kv_dtype="bf16"):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        arch="qwen1.5-0.5b", family="dense", n_layers=n_layers,
        d_model=D_MODEL, n_heads=N_HEADS, n_kv_heads=n_kv,
        head_dim=HEAD_DIM, d_ff=D_FF, vocab_size=151936, act="silu",
        gated_mlp=True, norm="rms", qkv_bias=True, rope=True,
        rope_theta=1e6, tie_embeddings=True, param_dtype=dtype,
        compute_dtype=dtype, attention_backend="pallas",
        kv_cache_dtype=kv_dtype)


@pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_paged_steps_update_pools_in_place(one_chip, monkeypatch, pool,
                                           step):
    """The donated paged decode step (48 rows) and prefill chunk (32
    tokens) at qwen1.5-0.5b widths, 4 layers, 256 pages of 16 (1024 for
    int8), 72 table pages, over f32, bf16 and int8 pools (the last with
    its scale side pools): no copy, dynamic-(update-)slice or buffer
    allocation of a layer's pool or of the stack, temporaries below one
    layer's pool, and every pool aliased into the outputs — a dispatch
    moves only the pool rows it writes or reads.  The one exception: the
    compiler may stage the small scale side pools in on-chip memory for
    the layer loop (an asynchronous copy in and out, in their own layout,
    no relayout)."""
    from repro.kernels import paged_attention as pa
    from repro.models import init_model, init_paged_cache
    from repro.serve.engine import make_paged_decode_step, make_paged_prefill
    monkeypatch.setattr(pa, "interpret_mode", lambda backend: False)
    # int8 pages are a quarter of f32 ones: 4x the pages keep the pool too
    # big for the compiler to stage it whole in on-chip memory, as a
    # deployment's pool is
    L, ps, P = 4, 16, 72
    n_pages = 1024 if pool == "int8" else 256
    B, S = (48, 1) if step == "decode" else (1, 32)
    dtype = "bfloat16" if pool == "bfloat16" else "float32"
    cfg = _qwen_cfg(L, N_KV, dtype, "int8" if pool == "int8" else "bf16")

    def place(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)
    params = place(jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg)))
    pools = place(jax.eval_shape(
        lambda: init_paged_cache(cfg, n_pages, ps))["layers"])
    fn = make_paged_decode_step(cfg) if step == "decode" \
        else make_paged_prefill(cfg)
    # float32 as the dense configuration serves it, at full precision
    with jax.default_matmul_precision(
            "highest" if dtype == "float32" else "default"):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, pools, _sds((B, S), jnp.int32, one_chip),
            _sds((B, P), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip)).compile()
    hlo = compiled.as_text()
    if step == "decode":             # prefill chunks take the gather path
        _assert_mosaic(compiled)
    leaves = pools["stack"]
    for name, leaf in leaves.items():
        assert _pool_moves(hlo, leaf,
                           staging_ok=name.startswith("scale_")) == []
    pool_k = leaves["pool_k"]
    layer_bytes = math.prod(pool_k.shape[1:]) * pool_k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    hlo_dt = {"float32": "f32", "bfloat16": "bf16", "int8": "s8"}
    shapes = {f"{hlo_dt[str(a.dtype)]}[{','.join(map(str, a.shape))}]"
              for a in leaves.values()}
    pool_params = {i for i, sh in enumerate(entry_param_shapes(hlo))
                   if sh in shapes}
    assert len(pool_params) == len(leaves)
    assert pool_params <= {a["param"] for a in input_output_aliases(hlo)}
    # lane-dense: a page is a (ps, n_kv·head_dim) tile, its scales one row
    assert pool_k.shape == (L, n_pages, ps, N_KV * HEAD_DIM)
    if pool == "int8":
        assert leaves["scale_k"].shape == (L, n_pages, N_KV * ps)


@pytest.mark.parametrize("n_kv", [16, 2])
def test_paged_decode_compiles_on_a_model4_mesh(topo, monkeypatch, n_kv):
    """The donated paged decode step (4 layers, f32, 48 rows) over a
    (data=1, model=4) mesh of the described 2x2 v5e, the Mosaic kernel
    inside shard_map.  16 kv heads shard the lane-dense pools in whole
    heads (4 a chip); 2 kv heads cannot, so the pools stay replicated and
    every chip attends both heads.  Either way no collective moves a
    pool's bytes, and the pools are aliased into the outputs in the
    placement they came in."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.analysis.hlo import collective_instrs
    from repro.kernels import paged_attention as pa
    from repro.launch.mesh import make_test_mesh
    from repro.models import init_model, init_paged_cache
    from repro.parallel.sharding import AXIS_MODEL, param_specs, set_mesh
    from repro.parallel.statesharding import cache_specs
    from repro.serve.engine import make_paged_decode_step
    monkeypatch.setattr(pa, "interpret_mode", lambda backend: False)
    L, ps, P_, n_pages, B = 4, 16, 72, 256, 48
    cfg = _qwen_cfg(L, n_kv, "float32")
    mesh = make_test_mesh(1, 4, devices=topo.devices)

    def tag(tree, specs):
        return jax.tree.map(lambda a, sh: _sds(a.shape, a.dtype, sh),
                            tree, specs)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    pools = jax.eval_shape(lambda: init_paged_cache(cfg, n_pages, ps))
    pools = pools["layers"]
    pools = tag(pools, cache_specs(pools, mesh, kv_heads=n_kv))
    rep = NamedSharding(mesh, P())
    with set_mesh(mesh), jax.default_matmul_precision("highest"):
        compiled = jax.jit(make_paged_decode_step(cfg),
                           donate_argnums=(1,)).lower(
            tag(params, param_specs(params, mesh)), pools,
            _sds((B, 1), jnp.int32, rep), _sds((B, P_), jnp.int32, rep),
            _sds((B,), jnp.int32, rep)).compile()
    hlo = compiled.as_text()
    _assert_mosaic(compiled)
    pool_k = pools["stack"]["pool_k"]
    want = (None, None, None, AXIS_MODEL if n_kv == 16 else None)
    assert tuple(pool_k.sharding.spec) == want
    out = compiled.output_shardings[1]["stack"]["pool_k"]
    assert out.is_equivalent_to(pool_k.sharding, pool_k.ndim)
    # one layer's pool on one of the 4 chips
    shard_bytes = math.prod(pool_k.shape[1:]) * pool_k.dtype.itemsize // 4
    assert [(op, n) for op, n, _ in collective_instrs(hlo)
            if n >= shard_bytes] == []
    assert len(input_output_aliases(hlo)) == 2
