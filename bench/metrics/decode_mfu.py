"""Layer: model step.  Model FLOPs of the traced decode steps over their
device time (``jit_step``) times the chip's bf16 peak (see
``bench.context.decode_mfu``).  Moves output_tokens_per_s."""
from bench.context import decode_mfu


def read(run):
    return decode_mfu(run)
