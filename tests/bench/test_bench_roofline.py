"""Operation and byte counts against hand-computed values at
qwen1.5-0.5b's shapes, and the peaks table."""
import pytest

from bench import roofline as R, spec


@pytest.fixture(scope="module")
def dims():
    return R.Dims.from_config(spec.load_json(
        f"{spec.BENCH}/configs/qwen1.5-0.5b.json"))


def test_dims_from_the_published_config(dims):
    assert (dims.layers, dims.d_model, dims.heads, dims.kv_heads,
            dims.head_dim, dims.d_ff, dims.vocab, dims.kv_bytes) == \
        (24, 1024, 16, 16, 64, 2816, 151936, 4)


def test_matmul_params(dims):
    # q, k, v, o: 4 x 1024^2; up, gate, down: 3 x 1024 x 2816
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert per_layer == 12_845_056
    assert dims.matmul_params() == 24 * 12_845_056 + 1024 * 151_936
    assert dims.matmul_params() == 463_863_808


def test_token_and_step_flops(dims):
    assert R.token_flops(dims, 100) == 2 * 463_863_808 + \
        4 * 24 * 16 * 64 * 100 == 937_558_016
    assert R.decode_step_flops(dims, [100, 100]) == 2 * 937_558_016
    # chunk of 32 real tokens at offset 64: 32*64 + 32*33/2 = 2576 keys
    assert R.prefill_chunk_flops(dims, 64, 32) == \
        2 * 463_863_808 * 32 + 4 * 24 * 16 * 64 * 2576
    assert R.prefill_chunk_flops(dims, 0, 1) == R.token_flops(dims, 1)


def test_paged_attention_call(dims):
    flops, nbytes = R.paged_attn_call(dims, [10, 20])
    assert flops == 4 * 16 * 64 * 30 == 122_880
    # K and V, f32, 30 keys x 16 heads x 64; q and out, 2 rows
    assert nbytes == 2 * 16 * 64 * 4 * 30 + 2 * 2 * 16 * 64 * 4 == 262_144


def test_encoded_matmul_call():
    ops, nbytes = R.encoded_matmul_call(48, 1024, 2816)
    assert ops == 276_824_064
    assert nbytes == 48 * 1024 + 1024 * 2816 + 4 * 48 * 2816 == 3_473_408


def test_roofline_and_peaks():
    p = R.peaks("TPU v5 lite")
    assert p == R.peaks("TPU v5e")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"]) == \
        (197e12, 394e12, 819e9)
    ops, nbytes = R.encoded_matmul_call(48, 1024, 2816)
    assert R.roofline_s(ops, nbytes, p["int8_ops"], p["hbm_bytes_per_s"]) \
        == pytest.approx(3_473_408 / 819e9)      # memory-bound
    with pytest.raises(KeyError):
        R.peaks("cpu")


def test_kv_pool_arithmetic(dims):
    """192 KiB of f32 K and V per token; a 16-token page is 3 MiB."""
    per_token = dims.layers * 2 * dims.kv_heads * dims.head_dim * \
        dims.kv_bytes
    assert per_token == 192 * 1024
    assert 16 * per_token == 3 * 2 ** 20
