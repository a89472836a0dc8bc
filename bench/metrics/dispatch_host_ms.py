"""Layer: serve/engine.py.  Host milliseconds a decode step spends
uploading its inputs and dispatching the jitted step: over the traced
decode steps, the mean of the program's ``serve.inputs`` and
``serve.dispatch`` spans outside any ``serve.prefill_chunk``
(``bench.host_spans.step_split``).  Moves itl_p95_ms."""
from bench.host_spans import SPANS, step_split


def read(run):
    split = step_split(run.spans(*SPANS), len(run.traced_steps()), run.note)
    return None if split is None else split["dispatch_host_ms"]
