"""Serving telemetry bundle: the observability surface of the engine.

``ServeTelemetry`` groups the pieces the continuous-batching ``Engine``
reports through (DESIGN.md §9):

  * ``registry`` — the ``repro.obs`` metrics registry.  Always live: the
    engine's counters/gauges/histograms replace its old raw ``metrics``
    dict, and ``Engine.stats()`` / the BENCH json emitters are snapshots
    of it.
  * ``tracer`` — the engine's span API (``repro.obs.Tracer``).  Its
    ``serve.*`` spans always go to the profiler's trace (one
    ``TraceAnnotation`` each, recorded only while a trace runs); the
    Chrome trace-event export is off by default, and its lifecycle hooks
    are guarded no-ops then.
  * ``drift`` — optional ``DriftMonitor``: online dense-vs-encoded top-1
    logit agreement, sampled every N steps, published as a gauge.
  * ``profile_dir`` — optional ``jax.profiler`` trace directory; the
    engine wraps ``run()`` in ``obs.profiler_trace``.

Track-id layout for the tracer: tid 0 = the engine loop (the nested
``serve.*`` spans of each step, DESIGN.md §9, with ``serve.draft`` /
``serve.verify`` per speculative round under ``spec_decode``, §10), and
one track per request (``req_tid``) carrying its lifecycle — the
contiguous ``queued`` → ``prefill`` → ``decode`` phase spans (whose
durations sum to the request latency by construction — the
reconciliation the telemetry bench checks) plus
submit/admit/first-token/evict/stall/COW instants.  Speculation adds
``spec_rounds``/``spec_draft_tokens``/``spec_accepted_tokens`` counters
and the ``spec_acceptance_rate`` gauge to the registry.
"""
from __future__ import annotations

from typing import Optional

from repro.obs import DriftMonitor, MetricsRegistry, Tracer

TID_ENGINE = 0
_TID_REQ_BASE = 16


def req_tid(rid: int) -> int:
    """Tracer track id for request ``rid`` (engine/device tracks are
    below the base)."""
    return _TID_REQ_BASE + rid


class ServeTelemetry:
    """Bundle of registry + tracer + drift/profiler knobs.

    Engines that are handed no telemetry build a disabled one: metrics
    still accumulate (they are the engine's bookkeeping now) but the
    Chrome tracer is off and no profiler runs.
    """

    def __init__(self, *, trace: bool = False,
                 drift: Optional[DriftMonitor] = None,
                 profile_dir: Optional[str] = None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        self.registry = registry if registry is not None else \
            MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(enabled=trace)
        self.drift = drift.bind(self.registry) if drift is not None else None
        self.profile_dir = profile_dir
        if self.tracer.enabled:
            self.tracer.thread(TID_ENGINE, "engine")

    @classmethod
    def disabled(cls) -> "ServeTelemetry":
        """Metrics-only telemetry (Chrome tracer off, no profiler)."""
        return cls()

    def write(self, trace_out: Optional[str] = None,
              metrics_out: Optional[str] = None,
              trace_jsonl: Optional[str] = None) -> None:
        """Export whatever was asked for (no-op for None paths)."""
        if trace_out:
            self.tracer.write_chrome(trace_out)
        if trace_jsonl:
            self.tracer.write_jsonl(trace_jsonl)
        if metrics_out:
            self.registry.write_json(metrics_out)
