"""Layer: model step.  Model FLOPs of the traced prefill chunks (real
tokens only, see ``bench.context.prefill_mfu``) over their device time
(``jit_prefill``) times the chip's bf16 peak, in the prefill-heavy batch
cell.  Moves prompt_tokens_per_s."""
from bench.context import prefill_mfu


def read(run):
    return prefill_mfu(run)
