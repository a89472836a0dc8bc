"""Peaks of the chip and the operations and bytes that the model and its
kernels need, computed from shapes.

These count the work the algorithm needs, not what today's implementation
does, so no later kernel can read over 100% of its roofline: padded rows,
idle slots, the encoded MAC's U bit planes and any recomputation are not
counted.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 394e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


@dataclasses.dataclass(frozen=True)
class Dims:
    """A dense decoder's sizes, read from a configuration file."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    kv_bytes: int = 4                 # bytes per cached K or V element

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        heads = int(c["num_attention_heads"])
        return cls(layers=int(c["num_hidden_layers"]),
                   d_model=int(c["hidden_size"]), heads=heads,
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c["hidden_size"]) // heads,
                   d_ff=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]),
                   kv_bytes=4 if c["torch_dtype"] == "float32" else 2)

    def linears(self) -> Tuple[Tuple[int, int], ...]:
        """(k, n) of one layer's linears: q, k, v, o, up, gate, down."""
        d, hd = self.d_model, self.head_dim
        return ((d, self.heads * hd), (d, self.kv_heads * hd),
                (d, self.kv_heads * hd), (self.heads * hd, d),
                (d, self.d_ff), (d, self.d_ff), (self.d_ff, d))

    def matmul_params(self) -> int:
        """Weights a token multiplies with: every layer's linears plus the
        output head (the embedding lookup multiplies nothing)."""
        per_layer = sum(k * n for k, n in self.linears())
        return self.layers * per_layer + self.d_model * self.vocab


def token_flops(dims: Dims, context: int) -> float:
    """Model FLOPs of one token that attends to ``context`` keys (itself
    included): 2 per weight, plus 4 per key, head dim and head for the
    scores and the weighted sum."""
    return 2.0 * dims.matmul_params() + \
        4.0 * dims.layers * dims.heads * dims.head_dim * context


def decode_step_flops(dims: Dims, contexts: Iterable[int]) -> float:
    """One decode step: one token for each active row, row b attending to
    ``contexts[b]`` keys."""
    return sum(token_flops(dims, c) for c in contexts)


def prefill_chunk_flops(dims: Dims, start: int, n: int) -> float:
    """A prefill chunk of ``n`` real tokens at offset ``start`` (padding
    is not work): token i attends to start + i + 1 keys."""
    keys = n * start + n * (n + 1) // 2
    return 2.0 * dims.matmul_params() * n + \
        4.0 * dims.layers * dims.heads * dims.head_dim * keys


def paged_attn_call(dims: Dims, contexts: Iterable[int]) -> Tuple[float,
                                                                  float]:
    """(flops, bytes) of one layer's paged attention over the active rows:
    K and V of each row's valid keys at the pool's dtype, plus q and the
    output in f32."""
    ctx = list(contexts)
    hd, h = dims.head_dim, dims.heads
    flops = 4.0 * h * hd * sum(ctx)
    kv = 2.0 * dims.kv_heads * hd * dims.kv_bytes * sum(ctx)
    return flops, kv + 2.0 * len(ctx) * h * hd * 4


def encoded_matmul_call(m: int, k: int, n: int) -> Tuple[float, float]:
    """(int8 ops, bytes) of one encoded matmul: int8 activation and weight
    codes in, f32 out."""
    return 2.0 * m * k * n, float(m * k + k * n + 4 * m * n)


def roofline_s(ops: float, nbytes: float, peak_ops: float,
               bytes_per_s: float) -> float:
    """Least time the chip needs: the larger of the compute and the
    memory bound."""
    return max(ops / peak_ops, nbytes / bytes_per_s)
