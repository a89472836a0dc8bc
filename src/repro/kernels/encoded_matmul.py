"""Pallas TPU kernel: fused encoded-MAC bitplane matmul.

The paper's encoding-based multiplier projects int8 operand pairs onto M wide
bits via single-level gates; on TPU this becomes (DESIGN.md §2): expand
activation codes into U {0,1} monomial planes (pure shift/AND — VPU), then
accumulate ``Σ_u A_u @ W̃_u`` on the MXU.  The fusion keeps HBM traffic at
int8 size: planes are expanded *in VMEM per tile*, never materialized in HBM
(the XLA path materializes a U× bitplane tensor).

Grid: (M/bm, N/bn, K/bk), K innermost so the f32 output tile stays resident
in VMEM across the K loop (revisited block).  Block shapes are MXU/VPU
aligned: int8 tiles (32,128)-multiples, bf16 (16,128)-multiples.

VMEM budget per step (defaults bm=bn=bk=128, U≤48):
  x tile 16 KiB + W̃ tile U·32 KiB (≤1.5 MiB) + out tile 64 KiB  « 16 MiB.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# BlockSpec index maps over the (M/bm, N/bn, K/bk) grid — module level so
# analysis/kernelcheck.py evaluates exactly what the kernel traces.

def x_block_map(i, j, kk):
    """x_codes (m, k): row block i, K block kk."""
    return (i, kk)


def w_block_map(i, j, kk):
    """wt (U, k, n): every monomial plane, K block kk, column block j."""
    return (0, kk, j)


def bias_block_map(i, j, kk):
    """bias (1, n): column block j (added once on the last K step).  A 2-D
    row, not a 1-D vector: Mosaic tiles a 1-D block differently from the
    XLA layout of a 1-D operand and refuses the call."""
    return (0, j)


def out_block_map(i, j, kk):
    """out (m, n): VMEM-resident across the K loop (revisited block)."""
    return (i, j)


def _kernel(x_ref, w_ref, b_ref, o_ref, *, mono_bits, n_k_blocks):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.int32)                     # (bm, bk)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for u, shifts in enumerate(mono_bits):               # static unroll (U)
        # variable-arity monomial: one shift/AND per distinct operand bit
        # (1-input IN/NOT gates and 2-input gates need no dummy shifts)
        word = x >> shifts[0]
        for s in shifts[1:]:
            word = word & (x >> s)
        plane = (word & 1).astype(jnp.bfloat16)
        acc += jnp.dot(plane, w_ref[u],                  # MXU, f32 accum
                       preferred_element_type=jnp.float32)
    o_ref[...] += acc

    @pl.when(k == n_k_blocks - 1)
    def _bias():
        o_ref[...] += b_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("mono_bits", "bm", "bn", "bk", "interpret"))
def encoded_matmul_pallas(x_codes: jnp.ndarray, wt: jnp.ndarray,
                          bias: jnp.ndarray, mono_bits: tuple,
                          bm: int = 128, bn: int = 128, bk: int = 128,
                          interpret: bool = False) -> jnp.ndarray:
    """x_codes (m,k) int8, wt (U,k,n) bf16/f32, bias (n,) → (m,n) f32.

    ``mono_bits``: tuple of per-monomial shift tuples, each 1–3 distinct bit
    positions — static (baked into the kernel as an unrolled loop; arity
    sets the shift/AND count, so low-arity gates cost fewer VPU ops).
    Caller pads shapes to block multiples (see ops.encoded_matmul).
    """
    m, k = x_codes.shape
    u, k2, n = wt.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel, mono_bits=mono_bits,
                               n_k_blocks=grid[2])
    bias2d = bias.astype(jnp.float32).reshape(1, n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), x_block_map),
            pl.BlockSpec((u, bk, bn), w_block_map),
            pl.BlockSpec((1, bn), bias_block_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), out_block_map),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="encoded_matmul_pallas",    # the device op's name in a trace
    )(x_codes, wt.astype(jnp.bfloat16), bias2d)
