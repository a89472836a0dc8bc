"""Jitted public wrappers for the Pallas kernels (padding + dispatch).

Backends: ``pallas`` compiles the kernel to Mosaic and needs a TPU (it
raises anywhere else — there is no silent fallback); ``pallas_interpret``
runs the same kernel in interpret mode (tests, CPU debugging);
``backend='xla'`` uses the single-GEMM einsum fold; ``auto`` picks
``pallas`` on TPU and the XLA lowering elsewhere.

``_pad_to`` is the one shared pad-to-block helper for both the encoded and
the flash wrappers.  Under an active mesh (parallel/sharding.set_mesh) the
encoded wrapper dispatches per the linear's tensor-parallel ``role``
(DESIGN.md §6): the Pallas kernel runs inside shard_map against the *local*
shard shapes — so padding/blocking never touches the global dims — and
row-parallel partial accumulations are psum-reduced before the bias.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import AXIS_MODEL, get_mesh
from .encoded_matmul import encoded_matmul_pallas
from .ref import planes_ref


def interpret_mode(backend: str) -> bool:
    """``interpret=`` flag for a Pallas backend name.  ``pallas`` is the
    compiled Mosaic kernel and raises off the TPU; only
    ``pallas_interpret`` interprets."""
    if backend == "pallas_interpret":
        return True
    if backend != "pallas":
        raise ValueError(f"unknown Pallas backend {backend!r}; expected "
                         "'pallas' or 'pallas_interpret'")
    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(
            f"backend 'pallas' compiles the Mosaic kernel and needs a TPU, "
            f"but JAX runs on {platform!r}; pass 'pallas_interpret' to run "
            "the kernel in interpret mode")
    return False


def _pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _norm_monos(mono_bits) -> tuple:
    """Normalize monomials to variable-arity tuples of distinct shifts.

    Accepts the padded ``(U, 3)`` array form (BitplaneProgram.a_mono_bits —
    padding repeats the last bit, AND-idempotent) or already-variable
    sequences of 1–3 bit positions; order within a monomial is preserved.
    """
    out = []
    for row in mono_bits:
        shifts = tuple(dict.fromkeys(int(b) for b in np.atleast_1d(row)))
        if not 1 <= len(shifts) <= 3:
            raise ValueError(f"monomial needs 1–3 distinct bits, got {row!r}")
        out.append(shifts)
    return tuple(out)


def _pad3(monos: tuple) -> np.ndarray:
    """(U, 3) int32 padded form (repeat last bit) for the vectorized paths."""
    return np.asarray([(m + (m[-1],) * 3)[:3] for m in monos], np.int32
                      ).reshape(-1, 3)


# m-dim block buckets: decode steps run tiny m (B=1..8 tokens), and padding
# every call up to 128 wastes >95% of the MXU rows — pick the smallest
# bucket that covers m instead.  m is a static (trace-time) shape, so each
# bucket compiles once.
_BM_BUCKETS = (8, 32, 128)


def _pick_bm(m: int) -> int:
    for b in _BM_BUCKETS:
        if m <= b:
            return b
    return _BM_BUCKETS[-1]


def _pallas_padded(x_codes, wt, bias, mono, bm, bn, bk, interpret):
    """Pad to block multiples, run the kernel, slice back."""
    m, n = x_codes.shape[0], wt.shape[2]
    xp = _pad_to(_pad_to(x_codes, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(wt, bk, 1), bn, 2)
    bp = _pad_to(bias, bn, 0)
    out = encoded_matmul_pallas(xp, wp, bp, mono, bm=bm, bn=bn, bk=bk,
                                interpret=interpret)
    return out[:m, :n]


def _pallas_sharded(x_codes, wt, bias, mono, role, mesh, bm, bn, bk,
                    interpret):
    """Shard-local Pallas dispatch over the model axis (DESIGN.md §6).

    column: W̃ and bias shard on n; every device runs the kernel on its
    (m, k) × (U, k, n/TP) slice and the output leaves n-sharded.
    row: x and W̃ shard on k; devices compute partial (m, n) accumulations
    against their local k slice (blocking/padding sees only k/TP) which are
    psum-reduced, then the replicated bias is added exactly once.
    """
    ax = AXIS_MODEL

    if role == "column":
        def col(xl, wl, bl):
            return _pallas_padded(xl, wl, bl, mono, bm, bn, bk, interpret)
        return jax.shard_map(col, mesh=mesh,
                             in_specs=(P(), P(None, None, ax), P(ax)),
                             out_specs=P(None, ax),
                             check_vma=False)(x_codes, wt, bias)

    def row(xl, wl, bl):
        zero = jnp.zeros_like(bl)
        part = _pallas_padded(xl, wl, zero, mono, bm, bn, bk, interpret)
        return jax.lax.psum(part, ax) + bl
    return jax.shard_map(row, mesh=mesh,
                         in_specs=(P(None, ax), P(None, ax, None), P()),
                         out_specs=P(), check_vma=False)(x_codes, wt, bias)


def encoded_matmul(x_codes: jnp.ndarray, wt: jnp.ndarray, bias: jnp.ndarray,
                   mono_bits, backend: str = "auto",
                   bm: int = None, bn: int = 128, bk: int = 128,
                   role: str = "replicated") -> jnp.ndarray:
    """Encoded matmul with pre-folded weights. Pads, dispatches, slices.

    x_codes (m,k) int8 · wt (U,k,n) · bias (n,) → (m,n) f32.
    ``mono_bits``: (U, 3) padded array or sequence of 1–3-bit monomial
    tuples (see _norm_monos).  ``bm=None`` picks the smallest m-block bucket
    covering m (decode-friendly; see _BM_BUCKETS).

    ``role`` is the linear's tensor-parallel role over the model axis
    (parallel.sharding.linear_role).  With an active mesh the XLA backend is
    partitioned by GSPMD from the operand shardings; the Pallas backends run
    shard-local via shard_map (row-parallel partials psum-reduced).  Falls
    back to the unsharded path when no mesh is active or the sharded dim
    does not divide the model axis.
    """
    m, k = x_codes.shape
    n = wt.shape[2]
    mono = _norm_monos(mono_bits)
    if bm is None:
        bm = _pick_bm(m)
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        A = planes_ref(x_codes, _pad3(mono)).astype(jnp.bfloat16)
        return jnp.einsum("umk,ukn->mn", A, wt.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32) + bias
    interpret = interpret_mode(backend)
    mesh = get_mesh()
    if mesh is not None and AXIS_MODEL in mesh.axis_names:
        tp = mesh.shape[AXIS_MODEL]
        if tp > 1 and ((role == "column" and n % tp == 0)
                       or (role == "row" and k % tp == 0)):
            return _pallas_sharded(x_codes, wt, bias, mono, role, mesh,
                                   bm, bn, bk, interpret)
    return _pallas_padded(x_codes, wt, bias, mono, bm, bn, bk, interpret)


def flash_mha(q, k, v, *, scale: float, causal: bool = True, window=None,
              cap=None, bq: int = 128, bk: int = 128,
              backend: str = "pallas"):
    """4-D GQA wrapper for the flash kernel: q (B,Sq,Hq,D), k/v (B,Sk,Hkv,D).

    KV heads are repeated to q heads (uniform grouping), (B,H) flattened to
    the kernel's leading dim, Sq/Sk padded to block multiples (padded keys
    masked by the causal/window test since their positions exceed all query
    positions... padded QUERIES are sliced off the output)."""
    from .flash_attention import flash_attention
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hq, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hq, Sk, D)
    qf = _pad_to(qf, bq, 1)
    kf = _pad_to(kf, bk, 1)
    vf = _pad_to(vf, bk, 1)
    if (-Sk) % bk and not causal:
        raise ValueError("non-causal padding needs an explicit kv mask")
    interpret = interpret_mode(backend)
    out = flash_attention(qf, kf, vf, scale=scale, causal=causal,
                          window=window, cap=cap, bq=bq, bk=bk,
                          interpret=interpret)
    out = out[:, :Sq].reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    return out
