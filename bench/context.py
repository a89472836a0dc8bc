"""What a per-layer metric reader is handed: the window, the host's step
records, the traced sub-window's summary and host spans, and the cell's
sizes.  A reader returns a number, or None when it finds nothing to read;
it never makes up a 0 for a share of a roofline or a peak."""
from __future__ import annotations

import dataclasses
import re
import sys
from typing import Dict, List, Optional, Tuple

from . import roofline as R
from .host_spans import host_spans
from .stats import Window
from .trace import Event, Summary
from .window import StepRecord


@dataclasses.dataclass
class Run:
    config: dict
    traffic: dict
    window: Window
    steps: List[StepRecord]
    traced: Tuple[int, int]              # [lo, hi) of steps under the trace
    summary: Optional[Summary]
    counters: Dict[str, float]           # engine counter deltas, window
    dims: R.Dims                         # the architecture module's dims
    peaks: dict
    # every host event of the traced sub-window: the harness's ``bench.*``
    # and ``engine.step`` spans, the program's ``serve.*`` spans, and any
    # other the process wrote
    events: List[Event] = dataclasses.field(default_factory=list)

    def traced_steps(self) -> List[StepRecord]:
        lo, hi = self.traced
        return self.steps[lo:hi] if lo >= 0 else []

    def decode_steps(self) -> List[StepRecord]:
        return [s for s in self.traced_steps() if s.decode_ctx]

    def chunks(self) -> List[tuple]:
        return [c for s in self.traced_steps() for c in s.chunks]

    def spans(self, *names: str) -> List[Event]:
        """The traced host spans named ``names``, by start (outermost
        first where two start together)."""
        return host_spans(self.events, names)

    def op_seconds(self, pattern: str) -> Tuple[float, int]:
        """Device seconds and count of traced ops matching ``pattern``."""
        rx = re.compile(pattern)
        hits = [e for e in self.summary.ops if rx.search(e.text)]
        return sum(e.dur_ns for e in hits) * 1e-9, len(hits)

    def note(self, msg: str) -> None:
        """A reader's diagnostic, on standard error."""
        print(f"[metric] {msg}", file=sys.stderr)


def program_seconds(run: Run, prefix: str, expected: int,
                    what: str) -> Optional[float]:
    """Device seconds of the traced executions of the jitted program
    ``prefix`` (``jit_step``, ``jit_prefill``), when their count matches
    the ``expected`` dispatches the host made while tracing."""
    if run.summary is None or expected == 0:
        return None
    secs, n = run.summary.module_seconds(prefix)
    if n != expected:
        run.note(f"{what}: {n} traced {prefix} executions, the host "
                 f"dispatched {expected}; not read")
        return None
    return secs


def decode_mfu(run: Run) -> Optional[float]:
    """Model FLOPs of the traced decode steps (2 per weight per active
    token, plus attention over each token's keys) over their device time
    (``jit_step``) times the chip's bf16 peak, in %."""
    steps = run.decode_steps()
    secs = program_seconds(run, "jit_step", len(steps), "decode_mfu")
    if not secs:
        return None
    flops = sum(R.decode_step_flops(run.dims, s.decode_ctx) for s in steps)
    return 100.0 * flops / (secs * run.peaks["bf16_flops"])


def prefill_mfu(run: Run) -> Optional[float]:
    """Model FLOPs of the traced prefill chunks, real tokens only, over
    their device time (``jit_prefill``) times the chip's bf16 peak, in
    %."""
    chunks = run.chunks()
    secs = program_seconds(run, "jit_prefill", len(chunks), "prefill_mfu")
    if not secs:
        return None
    flops = sum(R.prefill_chunk_flops(run.dims, s, n) for s, n in chunks)
    return 100.0 * flops / (secs * run.peaks["bf16_flops"])
