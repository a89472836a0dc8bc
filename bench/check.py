"""The comparison that decides ``correct``.

What the timed path produced is held against the plain reference (the
``Reference`` of the cell's architecture module, ``bench/arch``) on the
same weights, after the window has closed:

  token_gap   the widest gap by which a served token's reference logit
              lies below the reference's best logit at that position, over
              every served token of the sampled requests (greedy decoding:
              a correct server serves the reference's argmax, or a token
              within rounding of it)
  kv_rel_err  for sampled requests still in flight at the close, the K and
              V that chunked prefill and decode wrote into the paged pool,
              against the reference's: the worst leaf (one layer's K or V)
              of ||program - reference|| over the larger of that leaf's
              reference norm and the median leaf's

Each number has a limit in the configuration file (``check``), set from
the program's readings on a dozen seeds and its control's (see PERF.md).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp


@dataclasses.dataclass
class Served:
    """One sampled request: its prompt, the tokens served, and (in
    flight) the K and V the program holds for prompt + out[:-1], in rows
    0 .. len(prompt) + len(out) - 2 of arrays of one length per run."""
    prompt: np.ndarray
    out: List[int]
    kv: Optional[Tuple[np.ndarray, np.ndarray]] = None

    def stream(self) -> np.ndarray:
        return np.concatenate([self.prompt,
                               np.asarray(self.out[:-1], np.int32)])

    def targets(self) -> np.ndarray:
        """Per input position, the token served after it (0 where none)."""
        t = np.zeros((len(self.prompt) + len(self.out) - 1,), np.int32)
        t[len(self.prompt) - 1:] = self.out
        return t


def _served_rows(s: Served) -> slice:
    return slice(len(s.prompt) - 1, len(s.prompt) - 1 + len(s.out))


@jax.jit
def _leaf_norms(prog, ref, n):
    rows = min(prog.shape[1], ref.shape[1])
    prog, ref = prog[:, :rows], ref[:, :rows]
    live = (jnp.arange(rows) < n)[None, :, None, None]
    axes = tuple(range(1, ref.ndim))
    return (jnp.sqrt(jnp.sum(jnp.where(live, prog - ref, 0.0) ** 2, axes)),
            jnp.sqrt(jnp.sum(jnp.where(live, ref, 0.0) ** 2, axes)))


def _kv_err(prog, ref, n) -> Tuple[List[float], List[float]]:
    """Per-leaf (per-layer) norms of the difference and of the reference
    over the first ``n`` positions (shapes stay fixed, so one compile)."""
    d, r = _leaf_norms(jnp.asarray(prog), ref, n)
    return np.asarray(d).tolist(), np.asarray(r).tolist()


def compare(ref, sample: List[Served], length: int
            ) -> Dict[str, float]:
    """The program's numbers over ``sample`` against ``ref`` (an
    architecture module's ``Reference``); every sequence is padded to
    ``length`` (one compile per cell)."""
    gap, diffs, norms = 0.0, [], []
    for s in sample:
        toks = s.stream()
        mx, tg, _, ks, vs = ref.run(toks, length, s.targets())
        rows = _served_rows(s)
        gap = max(gap, float(np.max(mx[rows] - tg[rows])))
        if s.kv is not None:
            for prog, r in zip(s.kv, (ks, vs)):
                d, nr = _kv_err(prog, r, len(toks))
                diffs.append(d)
                norms.append(nr)
    out = {"token_gap": gap}
    if diffs:
        out["kv_rel_err"] = worst_leaf(diffs, norms)
        out["kv_rel_err_by_layer"] = by_layer(diffs, norms)
    return out


def leaf_errors(diffs: List[List[float]], norms: List[List[float]]
                ) -> np.ndarray:
    """||diff|| over max(||ref leaf||, median ||ref leaf||), per leaf:
    (leaf sets, layers)."""
    med = float(np.median(np.concatenate([np.asarray(n) for n in norms])))
    d, n = np.asarray(diffs), np.asarray(norms)
    return d / np.maximum(np.maximum(n, med), 1e-30)


def worst_leaf(diffs: List[List[float]], norms: List[List[float]]) -> float:
    """The worst leaf's error (see ``leaf_errors``)."""
    return float(leaf_errors(diffs, norms).max())


def by_layer(diffs: List[List[float]], norms: List[List[float]]
             ) -> List[float]:
    """The worst error of each layer over K, V and the sampled requests:
    how the difference grows with depth."""
    return leaf_errors(diffs, norms).max(axis=0).tolist()


def control(ref, ctl, sample: List[Served],
            length: int) -> Dict[str, float]:
    """The control's numbers: the control put in the program's place, on
    the same prompts and served tokens.  At each served position the gap
    of the token that the control puts first; its K and V against the
    reference's."""
    gap, diffs, norms = 0.0, [], []
    for s in sample:
        toks = s.stream()
        _, _, c_arg, c_ks, c_vs = ctl.run(toks, length, s.targets())
        rows = _served_rows(s)
        tgt = np.zeros_like(s.targets())
        tgt[rows] = c_arg[rows]
        mx, tg, _, ks, vs = ref.run(toks, length, tgt)
        gap = max(gap, float(np.max(mx[rows] - tg[rows])))
        if s.kv is not None:
            for c, r in zip((c_ks, c_vs), (ks, vs)):
                d, nr = _kv_err(c, r, len(toks))
                diffs.append(d)
                norms.append(nr)
    out = {"token_gap": gap}
    if diffs:
        out["kv_rel_err"] = worst_leaf(diffs, norms)
        out["kv_rel_err_by_layer"] = by_layer(diffs, norms)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            unread: Tuple[str, ...] = ()) -> Tuple[bool, Dict[str, dict]]:
    """Every limited number must be present, finite and within its
    limit, except those in ``unread``: the window left nothing for them
    to read (no request in flight at the close for ``kv_rel_err``)."""
    ok, table = True, {}
    for name, lim in limits.items():
        v = numbers.get(name)
        good = (v is None and name in unread) or \
            (v is not None and np.isfinite(v) and v <= lim)
        ok = ok and bool(good)
        table[name] = {"value": v, "limit": lim}
    return ok, table
