"""Layer: nn/moe.py.  The expert layers' grouped matmuls as a share of
their roofline over the traced decode steps: the least time the chip
needs, over the device time of the expert ops inside the traced
``jit_step`` executions.  Match: device ops named ``%moe_experts.<n>``
(the megablox kernel's Pallas call takes the name of the jitted function
around it, ``nn/moe.moe_experts``); three per expert layer per decode
step.

Per decode step, from the engine's counters over the whole window divided
by the window's decode steps (the counters are not split by step):
``moe_experts_active`` experts each read once (3·d·f weights at the
weights' dtype), ``moe_assignments`` routed rows each read in and written
out at every projection (3·(d + f) f32), and 2·3·d·f operations per
assignment at the bf16 peak (the peak ``decode_mfu`` uses).  Moves
output_tokens_per_s."""
import re

from bench import roofline as R

MATCH = r"^%moe_experts\."


def _step_ops(run):
    """The expert ops inside the traced decode programs, and how many of
    those programs ran; None where the trace has none."""
    steps = [m for m in run.summary.modules if m.name.startswith("jit_step")]
    if not steps:
        return None, 0
    rx = re.compile(MATCH)
    secs, n = 0.0, 0
    for e in run.summary.ops:
        if rx.search(e.text) and any(
                m.start_ns <= e.start_ns < m.end_ns for m in steps):
            secs += e.dur_ns * 1e-9
            n += 1
    return (secs, n), len(steps)


def read(run):
    steps = run.decode_steps()
    assign = run.counters.get("moe_assignments", 0)
    active = run.counters.get("moe_experts_active", 0)
    if run.summary is None or not steps or not assign:
        run.note("moe_experts_roofline: no routed work counted (counters "
                 "moe_assignments / moe_experts_active) or no decode step "
                 "traced; not read")
        return None
    window_steps = [s for s in run.steps
                    if s.t0 >= run.window.t_start and s.decode_ctx]
    if not window_steps:
        run.note("moe_experts_roofline: no decode step in the window; "
                 "not read")
        return None
    got, n_steps = _step_ops(run)
    d = run.config["hidden_size"]
    f = run.config["moe_intermediate_size"]
    layers = run.dims.layers
    if got is None or n_steps != len(steps) or \
            got[1] != 3 * layers * n_steps or got[0] <= 0:
        run.note(f"moe_experts_roofline: {got and got[1]} expert ops in "
                 f"{n_steps} traced jit_step executions, expected "
                 f"{3 * layers * len(steps)} in {len(steps)}; not read")
        return None
    per_step_assign = assign / len(window_steps)
    per_step_active = active / len(window_steps)
    w_bytes = 4 if run.config["torch_dtype"] == "float32" else 2
    nbytes = per_step_active * 3 * d * f * w_bytes + \
        per_step_assign * 3 * (d + f) * 4
    ops = per_step_assign * 2 * 3 * d * f
    p = run.peaks
    need = n_steps * R.roofline_s(ops, nbytes, p["bf16_flops"],
                                  p["hbm_bytes_per_s"])
    return 100.0 * need / got[0]
