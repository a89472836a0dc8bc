"""Bitplane-GEMM decomposition of an encoding circuit — the TPU-native path.

Every single-level gate output is a multilinear polynomial over operand bits
(idempotent algebra: b² = b).  Each monomial factors as

    (product of activation bits) × (product of weight bits)

so the encoded MAC over an (m,k)×(k,n) matmul becomes

    out = Σ_u  A_u(x) @ W̃_u(s, w)  + bias(s, w)

with ``A_u ∈ {0,1}^{m×k}`` computed by shift/AND on int8 codes (VPU-friendly,
no gather) and ``W̃_u ∈ ℝ^{k×n}`` folded offline from the circuit, the weight
bit-planes, and the position weights ``s`` (linear in ``s`` → autodiff gives
exact position-weight gradients).  Rank-1 (single-operand) and constant terms
fold into ``W̃``/``bias`` exactly, so the decomposition is *bit-exact* equal to
the LUT oracle.

This is the hardware adaptation of the paper's ASIC design: the wide-bit
projection becomes R dense {0,1} GEMM planes on the MXU; the per-column
decoder becomes the fold of ``s`` into ``W̃``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import gates as G
from .circuits import Circuit

Mono = frozenset  # frozenset[int] over operand-bit indices; {} == constant 1
Poly = dict       # Mono -> float


def _pmul(p: Poly, q: Poly) -> Poly:
    out: Poly = {}
    for ma, ca in p.items():
        for mb, cb in q.items():
            m = ma | mb                      # idempotent: b*b = b
            out[m] = out.get(m, 0.0) + ca * cb
    return {m: c for m, c in out.items() if c != 0.0}


def _padd(p: Poly, q: Poly, alpha: float = 1.0) -> Poly:
    out = dict(p)
    for m, c in q.items():
        out[m] = out.get(m, 0.0) + alpha * c
    return {m: c for m, c in out.items() if c != 0.0}


def _bit(i: int) -> Poly:
    return {frozenset({int(i)}): 1.0}


_ONE: Poly = {frozenset(): 1.0}


def gate_polynomial(gate_type: int, idx: np.ndarray) -> Poly:
    x0, x1, x2 = _bit(idx[0]), _bit(idx[1]), _bit(idx[2])
    if gate_type == G.SET:
        return dict(_ONE)
    if gate_type == G.IN:
        return x0
    if gate_type == G.NOT:
        return _padd(_ONE, x0, -1.0)
    if gate_type == G.AND2:
        return _pmul(x0, x1)
    if gate_type == G.OR2:
        return _padd(_padd(x0, x1), _pmul(x0, x1), -1.0)
    if gate_type == G.NAND2:
        return _padd(_ONE, _pmul(x0, x1), -1.0)
    if gate_type == G.NAND3:
        return _padd(_ONE, _pmul(_pmul(x0, x1), x2), -1.0)
    if gate_type == G.XOR3:
        def xor(p, q):
            return _padd(_padd(p, q), _pmul(p, q), -2.0)
        return xor(xor(x0, x1), x2)
    raise ValueError(f"unknown gate type {gate_type}")


@dataclasses.dataclass
class BitplaneProgram:
    """Static decomposition of a circuit into bilinear/rank-1/constant terms.

    Terms (P of them) map position weights s → coefficients via ``coeff_map``
    (P, M).  Term p couples activation monomial ``a_of[p]`` (index into
    ``a_mono_bits``; -1 = empty) with weight monomial ``b_of[p]`` (-1 = empty).
    Monomial bit lists are padded to length 3 by repetition (AND-idempotent).
    """
    bits_a: int
    bits_b: int
    m_bits: int
    a_mono_bits: np.ndarray      # (U, 3) int32 — shift amounts into x codes
    b_mono_bits: np.ndarray      # (V, 3) int32 — shift amounts into w codes
    coeff_map: np.ndarray        # (P, M) float32 — term coeffs, linear in s
    a_of: np.ndarray             # (P,) int32 in [-1, U)
    b_of: np.ndarray             # (P,) int32 in [-1, V)

    @property
    def n_a_planes(self) -> int:
        return int(self.a_mono_bits.shape[0])

    @property
    def n_b_planes(self) -> int:
        return int(self.b_mono_bits.shape[0])

    @property
    def a_mono_tuples(self) -> tuple:
        """Activation monomials as variable-arity tuples (1–3 distinct bits).

        ``a_mono_bits`` pads every monomial to 3 shifts by repeating the last
        bit (AND-idempotent); this strips the padding so the Pallas kernel
        emits one shift/AND per *distinct* bit (kernels/ops.encoded_matmul
        accepts either form)."""
        return tuple(tuple(dict.fromkeys(int(b) for b in row))
                     for row in self.a_mono_bits)

    # ---- runtime pieces (all jittable; s may be a traced array) ------------

    def scatter_coeffs(self, s: jnp.ndarray):
        """Coefficient tensors from s: (S_bil (U,V), S_a (U,), S_b (V,), c0)."""
        c = jnp.asarray(self.coeff_map) @ s.astype(jnp.float32)      # (P,)
        U, V = self.n_a_planes, self.n_b_planes
        a_of = jnp.asarray(self.a_of)
        b_of = jnp.asarray(self.b_of)
        bil = (a_of >= 0) & (b_of >= 0)
        aon = (a_of >= 0) & (b_of < 0)
        bon = (a_of < 0) & (b_of >= 0)
        con = (a_of < 0) & (b_of < 0)
        S_bil = jnp.zeros((U, V), jnp.float32).at[
            jnp.where(bil, a_of, 0), jnp.where(bil, b_of, 0)
        ].add(jnp.where(bil, c, 0.0))
        S_a = jnp.zeros((U,), jnp.float32).at[
            jnp.where(aon, a_of, 0)].add(jnp.where(aon, c, 0.0))
        S_b = jnp.zeros((V,), jnp.float32).at[
            jnp.where(bon, b_of, 0)].add(jnp.where(bon, c, 0.0))
        c0 = jnp.sum(jnp.where(con, c, 0.0))
        return S_bil, S_a, S_b, c0

    def planes(self, codes: jnp.ndarray, side: str) -> jnp.ndarray:
        """Monomial bit-planes of int codes.  (…,) int → (U|V, …) int8 {0,1}.

        Pure shift/AND — no gather; this is what the Pallas kernel computes
        in VMEM on the VPU.
        """
        mono = self.a_mono_bits if side == "a" else self.b_mono_bits
        mono = jnp.asarray(mono)                      # (U, 3)
        v = codes.astype(jnp.int32)[None]             # (1, …)
        sh = lambda i: v >> mono[(slice(None),) + (None,) * codes.ndim + (i,)]
        plane = sh(0) & sh(1) & sh(2) & 1
        return plane.astype(jnp.int8)

    def fold_weights(self, w_codes: jnp.ndarray, s: jnp.ndarray):
        """Fold circuit+s+weight-planes → (W̃ (U,k,n) f32, bias (n,) f32)."""
        k = w_codes.shape[0]
        S_bil, S_a, S_b, c0 = self.scatter_coeffs(s)
        Gv = self.planes(w_codes, "b").astype(jnp.float32)     # (V, k, n)
        # full f32: a TPU's default f32 matmul would round S to bf16
        hi = jax.lax.Precision.HIGHEST
        Wt = jnp.einsum("uv,vkn->ukn", S_bil, Gv, precision=hi) \
            + S_a[:, None, None]
        bias = jnp.einsum("v,vn->n", S_b, Gv.sum(axis=1), precision=hi) \
            + c0 * k
        return Wt, bias

    def apply(self, x_codes: jnp.ndarray, w_codes: jnp.ndarray,
              s: jnp.ndarray) -> jnp.ndarray:
        """Encoded matmul (XLA path): (m,k) × (k,n) int codes → (m,n) f32.

        Equals ``Σ_k lut[x[m,k], w[k,n]]`` bit-exactly (float-assoc aside).
        """
        Wt, bias = self.fold_weights(w_codes, s)
        A = self.planes(x_codes, "a").astype(jnp.bfloat16)     # (U, m, k)
        # Single dot_general contracting (u, k) — one MXU GEMM after folding.
        out = jnp.einsum("umk,ukn->mn", A, Wt.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        return out + bias

    def apply_f32(self, x_codes, w_codes, s):
        """f32-accurate variant (used by tests/oracle comparisons)."""
        Wt, bias = self.fold_weights(w_codes, s)
        A = self.planes(x_codes, "a").astype(jnp.float32)
        return jnp.einsum("umk,ukn->mn", A, Wt) + bias


def decompose(circuit: Circuit) -> BitplaneProgram:
    """Expand a circuit into a BitplaneProgram (static, numpy)."""
    ba = circuit.bits_a
    term_coeffs: dict[tuple, np.ndarray] = {}
    M = circuit.m_bits
    for j in range(M):
        poly = gate_polynomial(int(circuit.gate_types[j]), circuit.in_idx[j])
        for mono, coef in poly.items():
            ma = tuple(sorted(i for i in mono if i < ba))
            mb = tuple(sorted(i - ba for i in mono if i >= ba))
            key = (ma, mb)
            if key not in term_coeffs:
                term_coeffs[key] = np.zeros((M,), np.float32)
            term_coeffs[key][j] += coef

    a_monos = sorted({k[0] for k in term_coeffs if k[0]})
    b_monos = sorted({k[1] for k in term_coeffs if k[1]})
    a_index = {m: i for i, m in enumerate(a_monos)}
    b_index = {m: i for i, m in enumerate(b_monos)}

    def pad3(mono: tuple) -> list[int]:
        out = list(mono)
        while len(out) < 3:
            out.append(out[-1] if out else 0)
        return out

    a_bits = np.asarray([pad3(m) for m in a_monos] or
                        np.zeros((0, 3)), np.int32).reshape(-1, 3)
    b_bits = np.asarray([pad3(m) for m in b_monos] or
                        np.zeros((0, 3)), np.int32).reshape(-1, 3)

    keys = sorted(term_coeffs.keys())
    coeff = np.stack([term_coeffs[k] for k in keys]) if keys else \
        np.zeros((0, M), np.float32)
    a_of = np.asarray([a_index.get(k[0], -1) if k[0] else -1 for k in keys],
                      np.int32)
    b_of = np.asarray([b_index.get(k[1], -1) if k[1] else -1 for k in keys],
                      np.int32)
    return BitplaneProgram(circuit.bits_a, circuit.bits_b, M,
                           a_bits, b_bits, coeff.astype(np.float32),
                           a_of, b_of)
