"""Peaks of the chip and the operations and bytes that the model and its
kernels need, computed from shapes.

These count the work the algorithm needs, not what today's implementation
does, so no later kernel can read over 100% of its roofline: padded rows,
idle slots, the encoded MAC's U bit planes and any recomputation are not
counted.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Tuple

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
    """What the operation and byte counts need of a decoder, from its
    architecture module's ``dims(c)`` (``bench/arch``)."""
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    kv_bytes: int                      # bytes per cached K or V element
    # (k, n) of one layer's linears that a token multiplies, in the order
    # the program runs them
    linears: Tuple[Tuple[int, int], ...]
    # weights a token multiplies: every layer's linears (of its routed
    # experts only) plus the output head; the embedding lookup multiplies
    # nothing
    matmul_params: int
    # per layer, the most keys a token attends (a sliding window), or None
    windows: Tuple[Optional[int], ...]

    def __post_init__(self):
        if len(self.windows) != self.layers:
            raise ValueError(f"{len(self.windows)} windows for "
                             f"{self.layers} layers")

    def window_layers(self) -> List[Tuple[Optional[int], int]]:
        """(window, number of layers with it), in first-layer order."""
        out: Dict[Optional[int], int] = {}
        for w in self.windows:
            out[w] = out.get(w, 0) + 1
        return list(out.items())

    def attended(self, first: int, last: int) -> int:
        """Keys attended over every layer by tokens that would see
        ``first``, ``first + 1``, ..., ``last`` keys without a window; a
        layer's window caps each at its size."""
        total = 0
        for w, n in self.window_layers():
            if w is None or last <= w:
                keys = (first + last) * (last - first + 1) // 2
            elif first >= w:
                keys = w * (last - first + 1)
            else:
                keys = (first + w) * (w - first + 1) // 2 + w * (last - w)
            total += n * keys
        return total


def token_flops(dims: Dims, context: int) -> float:
    """Model FLOPs of one token that attends to ``context`` keys (itself
    included) in each layer, up to the layer's window: 2 per weight, plus
    4 per key, head dim and head for the scores and the weighted sum."""
    return 2.0 * dims.matmul_params + \
        4.0 * dims.heads * dims.head_dim * dims.attended(context, context)


def decode_step_flops(dims: Dims, contexts: Iterable[int]) -> float:
    """One decode step: one token for each active row, row b attending to
    ``contexts[b]`` keys."""
    return sum(token_flops(dims, c) for c in contexts)


def prefill_chunk_flops(dims: Dims, start: int, n: int) -> float:
    """A prefill chunk of ``n`` real tokens at offset ``start`` (padding
    is not work): token i attends to start + i + 1 keys, up to each
    layer's window."""
    return 2.0 * dims.matmul_params * n + \
        4.0 * dims.heads * dims.head_dim * dims.attended(start + 1,
                                                         start + n)


def paged_attn_call(dims: Dims, contexts: Iterable[int]) -> Tuple[float,
                                                                  float]:
    """(flops, bytes) of one layer's paged attention over the active rows:
    K and V of each row's ``contexts[b]`` keys at the pool's dtype, plus q
    and the output in f32."""
    ctx = list(contexts)
    hd, h = dims.head_dim, dims.heads
    flops = 4.0 * h * hd * sum(ctx)
    kv = 2.0 * dims.kv_heads * hd * dims.kv_bytes * sum(ctx)
    return flops, kv + 2.0 * len(ctx) * h * hd * 4


def paged_attn_step_s(dims: Dims, contexts: Iterable[int], peak_flops: float,
                      bytes_per_s: float) -> float:
    """Least time of one decode step's paged attention: one call per
    layer, each at its roofline over every row's keys up to the layer's
    window."""
    ctx = list(contexts)
    return sum(n * roofline_s(*paged_attn_call(
        dims, [c if w is None else min(c, w) for c in ctx]), peak_flops,
        bytes_per_s) for w, n in dims.window_layers())


def encoded_matmul_call(m: int, k: int, n: int) -> Tuple[float, float]:
    """(int8 ops, bytes) of one encoded matmul: int8 activation and weight
    codes in, f32 out."""
    return 2.0 * m * k * n, float(m * k + k * n + 4 * m * n)


def roofline_s(ops: float, nbytes: float, peak_ops: float,
               bytes_per_s: float) -> float:
    """Least time the chip needs: the larger of the compute and the
    memory bound."""
    return max(ops / peak_ops, nbytes / bytes_per_s)
