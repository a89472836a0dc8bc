"""Observability primitives (DESIGN.md §9): metrics registry, trace-event
recorder, shared order statistics, drift monitor, profiler hook.

Serving-specific wiring (track ids, the engine's metric names, the
telemetry bundle) lives in ``repro.serve.telemetry``; this package is
dependency-free of the serving stack so benchmarks and tools can use it
standalone.
"""
from .stats import percentile, percentiles
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      DEFAULT_BUCKETS)
from .trace import Tracer
from .jitcount import CompileTracker
from .drift import DriftMonitor, logit_agreement
from .profile import profiler_trace
