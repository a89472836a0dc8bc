"""Layer: serve/scheduler.py.  Host milliseconds an engine step spends
admitting requests and growing their page tables: over the traced steps,
the mean of the program's ``serve.admit`` and ``serve.grow`` spans
(``bench.host_spans.step_split``).  Moves itl_p95_ms."""
from bench.host_spans import SPANS, step_split


def read(run):
    split = step_split(run.spans(*SPANS), len(run.traced_steps()), run.note)
    return None if split is None else split["scheduler_host_ms"]
