"""Layer: model step.  The prefill chunks' share of the chip's bf16 peak
(see ``bench.context.prefill_mfu``) in the open-loop chat cell.  Moves
ttft_p95_ms."""
from bench.context import prefill_mfu


def read(run):
    return prefill_mfu(run)
