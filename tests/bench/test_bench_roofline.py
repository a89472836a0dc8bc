"""Operation and byte counts against hand-computed values at
qwen1.5-0.5b's shapes and, with sliding windows, at toy shapes; and the
peaks table."""
import dataclasses

import pytest

from bench import roofline as R, spec


@pytest.fixture(scope="module")
def dims():
    c = spec.load_json(f"{spec.BENCH}/configs/qwen1.5-0.5b.json")
    return spec.arch_module(c).dims(c)


def test_dims_from_the_published_config(dims):
    assert (dims.layers, dims.heads, dims.kv_heads, dims.head_dim,
            dims.kv_bytes) == (24, 16, 16, 64, 4)
    # q, k, v, o, up, gate, down
    assert dims.linears == ((1024, 1024), (1024, 1024), (1024, 1024),
                            (1024, 1024), (1024, 2816), (1024, 2816),
                            (2816, 1024))
    assert dims.windows == (None,) * 24


def test_matmul_params(dims):
    # q, k, v, o: 4 x 1024^2; up, gate, down: 3 x 1024 x 2816
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert per_layer == 12_845_056
    assert dims.matmul_params == 24 * 12_845_056 + 1024 * 151_936
    assert dims.matmul_params == 463_863_808


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


def _windowed(windows=(None, 8, None, 8)):
    return R.Dims(layers=len(windows), heads=4, kv_heads=2, head_dim=24,
                  kv_bytes=2, linears=((64, 96),), matmul_params=1000,
                  windows=windows)


def test_windowed_attention_counts_keys_up_to_the_window():
    d = _windowed()
    assert d.window_layers() == [(None, 2), (8, 2)]
    # a token with 20 keys: all 20 in a full layer, 8 in a windowed one
    assert R.token_flops(d, 20) == 2 * 1000 + 4 * 4 * 24 * (2 * 20 + 2 * 8)
    # chunk of 8 at offset 4: tokens see 5..12 keys, capped at 8
    full, win = sum(range(5, 13)), 5 + 6 + 7 + 8 * 5
    assert R.prefill_chunk_flops(d, 4, 8) == \
        2 * 1000 * 8 + 4 * 4 * 24 * (2 * full + 2 * win)
    # a decode step of rows with 3 and 20 keys: 23 keys in each full
    # layer, 3 + 8 in each windowed one; q and out of 2 rows in every layer
    qo = 2 * 2 * 4 * 24 * 4
    assert R.paged_attn_step_s(d, [3, 20], 1.0, 1e30) == \
        4 * 4 * 24 * (2 * 23 + 2 * 11)                  # compute-bound
    assert R.paged_attn_step_s(d, [3, 20], 1e30, 1.0) == \
        2 * 2 * 24 * 2 * (2 * 23 + 2 * 11) + 4 * qo     # memory-bound
    with pytest.raises(ValueError):
        dataclasses.replace(d, windows=(None,) * 3)


@pytest.mark.parametrize("first,last", [(1, 1), (1, 40), (5, 12), (9, 30),
                                        (30, 31), (3, 2)])
def test_attended_matches_a_plain_count(first, last):
    d = _windowed((None, 8, 1, 16))
    want = sum(c if w is None else min(c, w) for w in d.windows
               for c in range(first, last + 1))
    assert d.attended(first, last) == want
