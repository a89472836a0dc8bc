"""Layer: serve/engine.py.  Host milliseconds a decode step keeps the
device waiting: over the traced decode steps, the mean of the program's
``serve.step`` span less the ``serve.wait`` spans inside it
(``bench.host_spans.step_split``).  Moves itl_p95_ms."""
from bench.host_spans import SPANS, step_split


def read(run):
    split = step_split(run.spans(*SPANS), len(run.traced_steps()), run.note)
    return None if split is None else split["engine_host_ms"]
