"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e at qwen1.5-0.5b widths (d_model 1024, d_ff 2816, 16 heads of 64).

Nothing runs: the TPU compiler that ships with jaxlib compiles against a
described v5e topology, so a kernel that interpret mode accepts but Mosaic
refuses (layouts, shape casts, unsupported dtype casts) fails here on the
CPU.  The topology is described inside a fixture, never at import, so
every test worker collects the same tests and only the one that runs them
loads the TPU library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
    """The fused paged-attention kernel over pools in the compute dtype
    (f32 as configured), bf16, int8 and packed int4, for decode (Sq=1)
    and a speculative verify step at k=4 (Sq=5): 8 slots, 16-token
    pages, 16 table pages."""
    B, ps, P, n_pages = 8, 16, 16, 129
    quant = kv in ("int8", "int4")
    f = jax.jit(lambda q, pk, pv, pg, ln, *sc: paged_attn_pallas(
        q, pk, pv, pg, ln, jnp.int32(2 ** 30), scale=HEAD_DIM ** -0.5,
        G=N_HEADS // N_KV, scale_k=sc[0] if sc else None,
        scale_v=sc[1] if sc else None))
    dtype, width = ((jnp.uint8, HEAD_DIM // 2) if kv == "int4"
                    else (jnp.dtype(kv), HEAD_DIM))
    pool = _sds((n_pages, ps, N_KV, width), dtype, one_chip)
    args = [_sds((B, sq, N_HEADS, HEAD_DIM), jnp.float32, one_chip),
            pool, pool, _sds((B, P), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip)]
    if quant:
        args += [_sds((n_pages, ps, N_KV), jnp.float32, one_chip)] * 2
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
        return kernel(q, pk, pv, pg, ln, jnp.int32(2 ** 30),
                      scale=HEAD_DIM ** -0.5, G=N_HEADS // N_KV)
    pool = _sds((129, 16, N_KV, HEAD_DIM), jnp.float32, one_chip)
    compiled = jax.jit(renamed_outer).lower(
        _sds((8, 1, N_HEADS, HEAD_DIM), jnp.float32, one_chip), pool, pool,
        _sds((8, 16), jnp.int32, one_chip),
        _sds((8,), jnp.int32, one_chip)).compile()
    _assert_mosaic(compiled)
    assert "%paged_attn_pallas." in compiled.as_text()
