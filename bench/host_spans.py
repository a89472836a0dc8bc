"""The program's own host spans inside a traced window.

The engine writes a span at each layer boundary of a step into the
profiler's trace (``jax.profiler.TraceAnnotation``, on the device planes'
clock): ``serve.step`` and, nested in it, admission, prefill chunks, page
growth, input uploads, the jitted call's dispatch, the host's wait on the
device, the commit and the gauges.  This module reduces them to

  * the host time of a decode step that the device waits on, split into
    the scheduler's part and the model step's input and dispatch part;
  * the device's idle gaps, each piece put down to the innermost host span
    covering it (harness or program), so the idle that ``bench.trace``
    files under ``engine.step`` splits by what the engine was doing.

The names are kept here, not imported from the program: a span the
program renames or drops reads as missing, and the reduction returns None
with a note, never a 0.  The window, the busy time and every gap come
from ``bench.trace.summarize`` over the harness's spans alone, unchanged.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .trace import Event, Summary

STEP, ADMIT, CHUNK, GROW, INPUTS, DECODE, DISPATCH, WAIT, COMMIT, GAUGES = (
    "serve.step", "serve.admit", "serve.prefill_chunk", "serve.grow",
    "serve.inputs", "serve.decode", "serve.dispatch", "serve.wait",
    "serve.commit", "serve.gauges")
SPANS = (STEP, ADMIT, CHUNK, GROW, INPUTS, DECODE, DISPATCH, WAIT, COMMIT,
         GAUGES)


def host_spans(events: List[Event], names: Sequence[str]) -> List[Event]:
    """The host events named in ``names``, by start (outermost first
    where two start together)."""
    wanted = set(names)
    return sorted((e for e in events if e.name in wanted
                   and not e.plane.startswith("/device:")),
                  key=lambda e: (e.start_ns, -e.dur_ns))


def innermost_idle(summary: Summary, spans: List[Event]) -> List[list]:
    """Each idle gap of ``summary`` cut at the edges of ``spans`` (one
    host thread's nested spans), every piece put down to the innermost
    span covering it ("outside" if none): [[name, seconds]], most first.
    The pieces of a gap add up to the gap."""
    tot: Dict[str, float] = {}
    for g0, g1, _ in summary.gaps:
        cover = [e for e in spans if e.start_ns < g1 and e.end_ns > g0]
        cuts = sorted({g0, g1} | {t for e in cover
                                  for t in (e.start_ns, e.end_ns)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inner = [e for e in cover if e.start_ns <= mid <= e.end_ns]
            name = max(inner, key=lambda e: (e.start_ns, -e.dur_ns)).name \
                if inner else "outside"
            tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
    return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])]


def steps(events: List[Event]) -> List[Tuple[Event, List[Event]]]:
    """Each ``serve.step`` with the program spans inside it."""
    spans = host_spans(events, SPANS)
    out = []
    for s in (e for e in spans if e.name == STEP):
        inside = [e for e in spans if e is not s
                  and s.start_ns <= e.start_ns and e.end_ns <= s.end_ns]
        out.append((s, inside))
    return out


def _outside(spans: List[Event], outer: str) -> List[Event]:
    """``spans`` that no span named ``outer`` among them contains."""
    outs = [e for e in spans if e.name == outer]
    return [e for e in spans if not any(
        o.start_ns <= e.start_ns and e.end_ns <= o.end_ns for o in outs)]


def _ms(spans: List[Event], *names: str) -> float:
    return sum(e.dur_ns for e in spans if e.name in names) * 1e-6


def step_split(events: List[Event], n_steps: int,
               note: Callable[[str], None]) -> Optional[Dict[str, float]]:
    """Milliseconds of host work per traced step, from the program's
    spans, or None (with a note) when the trace does not hold one
    ``serve.step`` per step the host dispatched while tracing
    (``n_steps``), or holds no decode step:

      engine_host_ms     mean over decode steps of ``serve.step`` less the
                         ``serve.wait`` spans inside it: the step's host
                         time on its critical path, the device idle
      scheduler_host_ms  mean over all steps of ``serve.admit`` plus
                         ``serve.grow``
      dispatch_host_ms   mean over decode steps of ``serve.inputs`` plus
                         ``serve.dispatch`` outside any prefill chunk
    """
    found = steps(events)
    if len(found) != n_steps or n_steps == 0:
        note(f"host spans: {len(found)} traced {STEP} spans, the host "
             f"stepped {n_steps} times; not read")
        return None
    dec = [(s, inside) for s, inside in found
           if any(e.name == DECODE for e in inside)]
    if not dec:
        note(f"host spans: no {DECODE} among {len(found)} traced steps; "
             "not read")
        return None
    return {
        "engine_host_ms": sum(s.dur_ns * 1e-6 - _ms(inside, WAIT)
                              for s, inside in dec) / len(dec),
        "scheduler_host_ms": sum(_ms(inside, ADMIT, GROW)
                                 for _, inside in found) / len(found),
        "dispatch_host_ms": sum(_ms(_outside(inside, CHUNK), INPUTS,
                                    DISPATCH)
                                for _, inside in dec) / len(dec),
    }
