"""Rate and tail arithmetic over one measured window.

Every end-to-end number is taken over all the work and all the time of
the window: a rate is the work completed in the window over its length,
and a tail is the tail of every request (or every gap) that fell in it.
A request due in the window that has no first token when the window
closes counts at its age then; it is never dropped.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """q-th percentile with linear interpolation between order statistics
    (numpy's default method); None for no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class ReqRecord:
    """What the harness saw of one request."""
    index: int
    due: float                       # when it was due (host clock)
    submit: Optional[float] = None   # when the harness submitted it
    admit: Optional[float] = None    # when the scheduler admitted it
    tokens: List[float] = dataclasses.field(default_factory=list)
    rid: Optional[int] = None
    max_new: int = 0
    plen: int = 0
    rejected: bool = False

    @property
    def finished(self) -> bool:
        return len(self.tokens) >= self.max_new


@dataclasses.dataclass
class Window:
    t_start: float
    t_stop: float
    records: List[ReqRecord]
    prefill_tokens: int              # prompt tokens ingested in the window

    @property
    def seconds(self) -> float:
        return self.t_stop - self.t_start

    def due_in_window(self) -> List[ReqRecord]:
        return [r for r in self.records
                if self.t_start <= r.due <= self.t_stop]

    def output_tokens(self) -> int:
        return sum(1 for r in self.records for t in r.tokens
                   if self.t_start <= t <= self.t_stop)


def output_tokens_per_s(w: Window) -> float:
    return w.output_tokens() / w.seconds


def prompt_tokens_per_s(w: Window) -> float:
    return w.prefill_tokens / w.seconds


def ttfts_s(w: Window) -> List[float]:
    """First token minus due time, for every request due in the window;
    one still waiting at the close counts at its age then."""
    out = []
    for r in w.due_in_window():
        first = r.tokens[0] if r.tokens and r.tokens[0] <= w.t_stop \
            else w.t_stop
        out.append(first - r.due)
    return out


def itls_s(w: Window) -> List[float]:
    """Every gap between consecutive output tokens that both fell in the
    window, over every request."""
    out = []
    for r in w.records:
        ts = [t for t in r.tokens if w.t_start <= t <= w.t_stop]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def waits_s(w: Window, field: str) -> List[float]:
    """``field`` (``submit`` or ``admit``) minus due time for every request
    due in the window; one that has not got there by the close counts at
    its age then."""
    out = []
    for r in w.due_in_window():
        t = getattr(r, field)
        out.append((t if t is not None and t <= w.t_stop else w.t_stop)
                   - r.due)
    return out


def _ms_p95(values: List[float]) -> Optional[float]:
    v = percentile(values, 95)
    return None if v is None else 1e3 * v


END_TO_END = {
    "output_tokens_per_s": output_tokens_per_s,
    "prompt_tokens_per_s": prompt_tokens_per_s,
    "ttft_p95_ms": lambda w: _ms_p95(ttfts_s(w)),
    "itl_p95_ms": lambda w: _ms_p95(itls_s(w)),
}


def end_to_end(w: Window, names: Sequence[str]) -> Dict[str, float]:
    """The window's end-to-end metrics by name (``setup_s`` is the
    harness's own and not computed here); a tail with no sample in the
    window is left out."""
    out = {n: END_TO_END[n](w) for n in names if n in END_TO_END}
    return {n: float(v) for n, v in out.items() if v is not None}
