"""Engine span API with two sinks: the profiler's trace and a Chrome
trace-event JSON + JSONL.

``Tracer.span(name, ...)`` always enters a ``jax.profiler.TraceAnnotation``
of that name: while a profiler trace runs (``--profile-dir``, or the
benchmark's traced window) the span lands in it on the same clock as the
device planes, so host time can be set against device time; with no trace
running the annotation records nothing.  When the tracer is enabled
(``--trace-out``) the same span is also recorded as a Chrome *complete*
event, so a serving run can be opened directly in Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing``: one track per request
plus the engine track, spans for queue wait / prefill chunks / decode
steps, instants for evictions, stalls, and COW copies.

Overhead contract: a disabled tracer costs one ``TraceAnnotation`` per
span (about half a microsecond on a host CPU with no profiler running)
and records nothing; ``complete``/``instant`` return before touching the
event list.  Those two stay Chrome-only: they serve events that cannot be
context managers (request lifecycle phases written after the fact, and
instants), and hot call sites guard them on ``tracer.enabled`` so even
the timestamp reads and args dicts are skipped.

All Chrome spans are emitted as *complete* events (``ph: "X"`` — one
record carrying both start and duration), so begin/end matching holds by
construction; timestamps are microseconds relative to the tracer's
creation on one monotonic clock (``time.perf_counter``).
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation


class _Span:
    """An enabled tracer's span: the profiler annotation plus a Chrome
    complete event on exit."""
    __slots__ = ("tr", "name", "tid", "cat", "args", "t0", "ann")

    def __init__(self, tr, name, tid, cat, args):
        self.tr, self.name, self.tid = tr, name, tid
        self.cat, self.args = cat, args
        self.ann = TraceAnnotation(name, **(args or {}))

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = self.tr.now()
        return self

    def set_metadata(self, **args) -> None:
        """Args known only at the span's end, in both sinks (the name
        matches ``TraceAnnotation.set_metadata``, which a disabled
        tracer's span is)."""
        self.ann.set_metadata(**args)
        self.args = {**(self.args or {}), **args}

    def __exit__(self, *exc):
        self.tr.complete(self.name, self.t0, self.tr.now(),
                         tid=self.tid, cat=self.cat, args=self.args)
        self.ann.__exit__(*exc)
        return False


class Tracer:
    """Append-only trace-event recorder on one monotonic clock.

    Track layout (``tid``): 0 = engine loop, and one track per request
    via ``repro.serve.telemetry.req_tid``.  ``pid`` is always 0 (single
    process).
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.t0 = time.perf_counter()
        self.events: List[dict] = []
        self._threads: Dict[int, str] = {}

    # ---- clock -------------------------------------------------------------

    def now(self) -> float:
        """Seconds on the tracer's clock (``time.perf_counter``)."""
        return time.perf_counter()

    def _us(self, t_s: float) -> float:
        return (t_s - self.t0) * 1e6

    # ---- recording ---------------------------------------------------------

    def thread(self, tid: int, name: str) -> None:
        """Name a track (rendered as the thread name in Perfetto)."""
        if not self.enabled:
            return
        self._threads.setdefault(tid, name)

    def span(self, name: str, tid: int = 0, cat: str = "",
             args: Optional[dict] = None):
        """Context manager measuring a span: a ``TraceAnnotation`` named
        ``name`` with ``args`` as its metadata (keep them few and
        scalar), plus a Chrome complete event when enabled.  Either kind
        takes ``set_metadata(**args)`` for args known only at the end."""
        if not self.enabled:
            return TraceAnnotation(name, **(args or {}))
        return _Span(self, name, tid, cat, args)

    def complete(self, name: str, t_start_s: float, t_end_s: float,
                 tid: int = 0, cat: str = "",
                 args: Optional[dict] = None) -> None:
        """One complete ('X') span from perf_counter seconds."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "X", "pid": 0, "tid": tid,
              "ts": self._us(t_start_s),
              "dur": max(0.0, (t_end_s - t_start_s) * 1e6)}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, tid: int = 0, cat: str = "",
                args: Optional[dict] = None,
                t_s: Optional[float] = None) -> None:
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "pid": 0, "tid": tid, "s": "t",
              "ts": self._us(self.now() if t_s is None else t_s)}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        self.events.append(ev)

    # ---- export ------------------------------------------------------------

    def chrome_events(self) -> List[dict]:
        """Thread-name metadata + every recorded event (Chrome trace-event
        array form)."""
        meta = [{"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                 "args": {"name": name}}
                for tid, name in sorted(self._threads.items())]
        return meta + list(self.events)

    def write_chrome(self, path: str) -> None:
        """JSON object form: ``{"traceEvents": [...]}`` — what Perfetto
        and chrome://tracing load directly."""
        with open(path, "w") as f:
            json.dump({"traceEvents": self.chrome_events(),
                       "displayTimeUnit": "ms"}, f, default=float)

    def write_jsonl(self, path: str) -> None:
        """One event object per line (stream-appendable form)."""
        with open(path, "w") as f:
            for ev in self.chrome_events():
                f.write(json.dumps(ev, default=float) + "\n")
