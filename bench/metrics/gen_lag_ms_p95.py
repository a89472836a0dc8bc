"""Layer: harness load generator.  How late the generator submitted
requests: 95th percentile of submit minus due time (host clock), over
every request due in the window.  Open loop only; moves ttft_p95_ms."""
from bench.stats import percentile, waits_s


def read(run):
    if run.traffic["loop"] != "open":
        return None
    v = percentile(waits_s(run.window, "submit"), 95)
    return None if v is None else 1e3 * v
