"""Layer: kernels/paged_attention.py.  The fused paged-attention kernel's
share of its roofline over the traced decode steps: the least time the
chip needs for the K/V bytes of each active row's valid keys (pool dtype;
up to each layer's window) plus q and out, and 4·keys·heads·head_dim
FLOPs, over the device time of the kernel's ops.  Match: device ops named
``%paged_attn_pallas.<n>`` (the Pallas call takes the name of the jitted
function around it, ``kernels/paged_attention.paged_attn_pallas``); one
per layer per decode step.  Moves output_tokens_per_s."""
from bench import roofline as R

MATCH = r"^%paged_attn_pallas\."


def read(run):
    steps = run.decode_steps()
    if run.summary is None or not steps:
        return None
    secs, n = run.op_seconds(MATCH)
    want = len(steps) * run.dims.layers
    if n != want or secs <= 0:
        run.note(f"paged_attn_roofline: {n} kernel ops traced, expected "
                 f"{want}; not read")
        return None
    p = run.peaks
    need = sum(R.paged_attn_step_s(run.dims, s.decode_ctx, p["bf16_flops"],
                                   p["hbm_bytes_per_s"]) for s in steps)
    return 100.0 * need / secs
