"""Layer: model step.  Device milliseconds per decode-step call: the
traced executions of the jitted decode program (``jit_step``) over their
count.  Moves itl_p95_ms."""
from bench.context import program_seconds


def read(run):
    n = len(run.decode_steps())
    secs = program_seconds(run, "jit_step", n, "decode_step_ms")
    return None if secs is None else 1e3 * secs / n
