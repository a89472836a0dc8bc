"""Drive the serving engine through one measured window, from one thread.

Before each ``engine.step()`` the driver submits every request that is due
(open loop) or whose stream is free (closed loop); after it, it reads the
new tokens of every request in flight.  A closed loop's set-up first drains
the first prefill wave (``Driver.ramp``), so its window opens on decode.
Host spans around each part go into the profiler's trace
(``jax.profiler.TraceAnnotation``) so a traced run can say what the host
was doing while the device sat idle.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax

from .stats import ReqRecord, Window
from .traffic import Req

SPAN_SUBMIT, SPAN_STEP, SPAN_READ, SPAN_WAIT = (
    "bench.submit", "engine.step", "bench.read_tokens", "bench.wait")


@dataclasses.dataclass
class StepRecord:
    """What one engine step did, from the host's side."""
    t0: float
    t1: float
    decode_ctx: List[int]        # keys attended by each decoded token
    chunks: List[tuple]          # (start, n) of each prefill chunk


@dataclasses.dataclass
class Trace:
    """A sub-window of the run under the profiler."""
    at_s: float                  # seconds after the window opens
    seconds: float
    start: Callable[[], None]
    stop: Callable[[], None]
    step_lo: int = -1            # steps dispatched while tracing
    step_hi: int = -1
    start_cost_s: float = 0.0    # the window's stall for starting it


def _counter(engine, name: str) -> int:
    return int(engine.metrics[name])


class Driver:
    def __init__(self, engine, requests: List[Req], loop: str,
                 clock=time.perf_counter):
        self.engine, self.loop, self.clock = engine, loop, clock
        self.requests = requests
        self.records: List[ReqRecord] = []
        self.steps: List[StepRecord] = []
        self.live: Dict[int, ReqRecord] = {}     # rid → record in flight
        self.rejects = 0
        self.counters: Dict[str, float] = {}     # engine counters, window
        self.queues: Dict[int, List[Req]] = {}   # closed loop: stream → own
        self.busy_streams: Dict[int, int] = {}   # stream → rid in flight
        if loop == "closed":
            for r in requests:
                self.queues.setdefault(r.stream, []).append(r)

    # ---- submission --------------------------------------------------------
    def _submit(self, req: Req, due: float) -> None:
        rec = ReqRecord(index=req.index, due=due, max_new=req.max_new,
                        plen=len(req.prompt))
        self.records.append(rec)
        rec.submit = self.clock()
        try:
            rec.rid = self.engine.submit(req.prompt, max_new=req.max_new)
        except ValueError:
            rec.rejected = True
            self.rejects += 1
            return
        self.live[rec.rid] = rec

    def _submit_free_streams(self) -> None:
        """Closed loop: the next request of every stream with none in
        flight."""
        for s, q in self.queues.items():
            rid = self.busy_streams.get(s)
            if rid is not None and rid in self.live:
                continue
            if q:
                self._submit(q.pop(0), self.clock())
                self.busy_streams[s] = self.records[-1].rid

    # ---- reading -----------------------------------------------------------
    def _read(self, now: float) -> None:
        reqs = self.engine.requests
        for rid in list(self.live):
            rec, r = self.live[rid], reqs[rid]
            if rec.admit is None and r.t_admit is not None:
                rec.admit = r.t_admit
            new = len(r.out) - len(rec.tokens)
            if new > 0:
                rec.tokens.extend([now] * new)
            if rec.finished:
                del self.live[rid]

    def _step(self) -> None:
        eng, reqs = self.engine, self.engine.requests
        before = {rid: (reqs[rid].state, reqs[rid].n_cached,
                        len(reqs[rid].out)) for rid in self.live}
        t0 = self.clock()
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            eng.step()
        t1 = self.clock()
        ctx, chunks = [], []
        for rid, (state, cached, n_out) in before.items():
            r = reqs[rid]
            new_out = len(r.out) - n_out
            if state == "decoding":
                if new_out > 0:
                    ctx.append(cached + 1)
            elif r.n_cached > cached or new_out > 0:
                # a prefill chunk; the one that completes the prompt yields
                # the first token, and the same step may decode one more
                start = 0 if state == "queued" else cached
                end = r.n_cached - max(0, new_out - 1)
                chunks.append((start, end - start))
                if new_out > 1:
                    ctx.append(end + 1)
        self.steps.append(StepRecord(t0, t1, ctx, chunks))
        with jax.profiler.TraceAnnotation(SPAN_READ):
            self._read(t1)

    # ---- set-up -------------------------------------------------------------
    def ramp(self) -> int:
        """Closed loop, before the window: send every stream's first
        request and step until no admitted request is still prefilling.
        Returns the steps taken."""
        n0 = len(self.steps)
        while True:
            with jax.profiler.TraceAnnotation(SPAN_SUBMIT):
                self._submit_free_streams()
            self._step()
            if not self.engine.sched.prefilling():
                return len(self.steps) - n0

    # ---- the window --------------------------------------------------------
    def run(self, seconds: float, trace: Optional[Trace] = None) -> Window:
        eng = self.engine
        pf0 = _counter(eng, "prefill_tokens")
        t_start = self.clock()
        t_end = t_start + seconds
        pending = list(self.requests)
        nxt = 0
        tracing = False
        while True:
            now = self.clock()
            if now >= t_end:
                break
            if trace is not None and not tracing and trace.step_lo < 0 \
                    and now >= t_start + trace.at_s:
                trace.start()
                tracing, trace.step_lo = True, len(self.steps)
                trace.start_cost_s = self.clock() - now
            elif tracing and now >= t_start + trace.at_s + trace.seconds:
                trace.stop()
                tracing, trace.step_hi = False, len(self.steps)
            with jax.profiler.TraceAnnotation(SPAN_SUBMIT):
                if self.loop == "open":
                    while nxt < len(pending) and \
                            t_start + pending[nxt].due_s <= now:
                        self._submit(pending[nxt],
                                     t_start + pending[nxt].due_s)
                        nxt += 1
                else:
                    self._submit_free_streams()
            if eng.busy:
                self._step()
            else:
                with jax.profiler.TraceAnnotation(SPAN_WAIT):
                    wake = t_end if self.loop != "open" or \
                        nxt >= len(pending) else \
                        min(t_end, t_start + pending[nxt].due_s)
                    time.sleep(max(0.0, wake - self.clock()))
        t_stop = self.clock()
        if tracing:
            trace.stop()
            trace.step_hi = len(self.steps)
        return Window(t_start=t_start, t_stop=t_stop, records=self.records,
                      prefill_tokens=_counter(eng, "prefill_tokens") - pf0)
