"""Layer: serve/scheduler.py.  95th percentile of admission into a slot
minus due time (the scheduler's admit stamp, host clock), over every
request due in the window; one not admitted by the close counts at its
age then.  Moves ttft_p95_ms."""
from bench.stats import percentile, waits_s


def read(run):
    v = percentile(waits_s(run.window, "admit"), 95)
    return None if v is None else 1e3 * v
