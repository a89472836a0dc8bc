"""Layer: kernels/encoded_matmul.py.  The encoded-MAC kernel's share of
its roofline over the traced steps: per call the int8 activation and
weight codes in and the f32 output (m·k + k·n + 4·m·n bytes) and 2·m·k·n
operations at the int8 peak, m being the real tokens (active decode rows,
real prefill tokens), over the device time of the kernel's ops.  Match:
device ops named ``%encoded_matmul_pallas.<n>`` (the Pallas call takes
the name of the jitted function around it,
``kernels/encoded_matmul.encoded_matmul_pallas``); one per encoded linear
per layer per dispatch.  Moves output_tokens_per_s."""
from bench import roofline as R

MATCH = r"^%encoded_matmul_pallas\."


def read(run):
    if run.summary is None or not run.config.get("encoded"):
        return None
    steps = run.traced_steps()
    ms = [len(s.decode_ctx) for s in steps if s.decode_ctx]
    ms += [n for s in steps for _, n in s.chunks]
    secs, n = run.op_seconds(MATCH)
    lin = run.dims.linears
    want = len(ms) * len(lin) * run.dims.layers
    if n != want or secs <= 0:
        run.note(f"encoded_matmul_roofline: {n} kernel ops traced, "
                 f"expected {want}; not read")
        return None
    p = run.peaks
    need = sum(run.dims.layers * R.roofline_s(
        *R.encoded_matmul_call(m, k, nn), p["int8_ops"],
        p["hbm_bytes_per_s"]) for m in ms for k, nn in lin)
    return 100.0 * need / secs
