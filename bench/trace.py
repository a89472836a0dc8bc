"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, its idle gaps and what the host was doing
in them, the time of each device op, and the executions of each jitted
program; the host's spans are read as they are (``context.Run.spans``).

A device op is an event on a device plane's ``XLA Ops`` line; a program
execution is one on its ``XLA Modules`` line.  Busy time is the union of
op intervals inside the traced window, which runs from the start of the
first host span that the harness wrote to the end of the last.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    text: str = ""               # name plus the event's string stats

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str,
                host_spans: Optional[Sequence[str]] = None) -> List[Event]:
    """Device planes' events in full, and the host events named in
    ``host_spans`` (every host event when None)."""
    from jax.profiler import ProfileData
    out = []
    wanted = None if host_spans is None else set(host_spans)
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if not device and wanted is not None and \
                        ev.name not in wanted:
                    continue
                text = ev.name
                if device:
                    text += " " + " ".join(str(v) for _, v in ev.stats
                                           if isinstance(v, str))
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 text))
    return out


def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                     float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Summary:
    window_ns: Tuple[float, float]
    devices: List[str]
    busy_s: float                        # mean over devices
    ops: List[Event]                     # device ops inside the window
    modules: List[Event]                 # program executions
    gaps: List[Tuple[float, float, str]]  # idle (start, end, host span)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, prefix: str) -> Tuple[float, int]:
        """Device seconds and count of executions of programs whose name
        starts with ``prefix`` (``jit_step``, ``jit_prefill``)."""
        hits = [e for e in self.modules if e.name.startswith(prefix)]
        return sum(e.dur_ns for e in hits) * 1e-9, len(hits)

    def top_ops(self, k: int = 10) -> List[list]:
        """The ``k`` device ops with the most self time (a loop's own time,
        not its body's), summed over executions by op name."""
        tot: Dict[str, float] = {}
        for e, self_ns in self_times(self.ops):
            name = e.name.split(" = ")[0]
            tot[name] = tot.get(name, 0.0) + self_ns * 1e-9
        return [[n, s] for n, s in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def idle_by_span(self, k: int = 10) -> List[list]:
        tot: Dict[str, float] = {}
        for s, e, name in self.gaps:
            tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
        return [[n, s] for n, s in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:k]]


def self_times(ops: List[Event]) -> List[Tuple[Event, float]]:
    """Each op with its duration less that of the ops nested in it on the
    same device line (a ``while`` holds its body's ops)."""
    out: Dict[int, float] = {}
    for key in sorted({(e.plane, e.line) for e in ops}):
        stack: List[Event] = []
        for e in sorted((e for e in ops if (e.plane, e.line) == key),
                        key=lambda e: (e.start_ns, -e.dur_ns)):
            while stack and stack[-1].end_ns <= e.start_ns:
                stack.pop()
            out[id(e)] = e.dur_ns
            if stack:
                out[id(stack[-1])] -= e.dur_ns
            stack.append(e)
    return [(e, out[id(e)]) for e in ops]


def summarize(events: List[Event], host_spans: Sequence[str],
              devices: Optional[Sequence[str]] = None) -> Summary:
    """Reduce one traced window.  ``devices``: the device planes to count
    (default: every device plane that ran an op)."""
    spans = [e for e in events if e.name in set(host_spans)
             and not e.plane.startswith("/device:")]
    if not spans:
        raise ValueError("the trace holds none of the harness's host spans")
    w0 = min(e.start_ns for e in spans)
    w1 = max(e.end_ns for e in spans)
    ops = [e for e in events if e.plane.startswith("/device:")
           and e.line == OPS_LINE and e.end_ns > w0 and e.start_ns < w1]
    mods = [e for e in events if e.plane.startswith("/device:")
            and e.line == MODULES_LINE and e.end_ns > w0 and e.start_ns < w1]
    planes = sorted(devices or {e.plane for e in ops})
    spans.sort(key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    busy, gaps = 0.0, []
    for p in planes:
        iv = union_ns((max(e.start_ns, w0), min(e.end_ns, w1))
                      for e in ops if e.plane == p)
        busy += sum(e - s for s, e in iv)
        if p != planes[0]:
            continue
        edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((s, e, _host_at((s + e) / 2, spans, starts)))
    busy_s = busy * 1e-9 / max(len(planes), 1)
    return Summary((w0, w1), planes, busy_s, ops, mods, gaps)


def _host_at(t: float, spans: List[Event], starts: List[float]) -> str:
    """The harness span around time t ("outside" if none).  The harness's
    spans follow one another on one thread, so the last one to start
    before t is the only candidate."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i].end_ns >= t:
        return spans[i].name
    return "outside"
