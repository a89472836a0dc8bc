"""Device-profiler hook: optional ``jax.profiler`` trace around serving.

``profiler_trace(dir)`` wraps a serving run in a
``jax.profiler.start_trace``/``stop_trace`` pair when a directory is
given (``launch/serve.py --profile-dir``), and is a no-op otherwise.
The resulting TensorBoard/XPlane dump attributes time *inside* the
jitted steps (per-op device time) and holds the engine's ``serve.*``
host spans on the same clock, so a gap in the device's work can be put
down to the host work around it (DESIGN.md §9).

A trace that was asked for and cannot start or stop is an error: the
exception propagates, so a profiled run never exits 0 without its trace.
"""
from __future__ import annotations

import contextlib
from typing import Optional


@contextlib.contextmanager
def profiler_trace(profile_dir: Optional[str] = None):
    """Context manager: jax profiler trace into ``profile_dir`` (no-op
    when None/empty).  Yields True iff the profiler started; start and
    stop failures raise."""
    if not profile_dir:
        yield False
        return
    import jax
    jax.profiler.start_trace(profile_dir)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()
