"""The trace reduction on a small trace recorded on a TPU v5e (one prefill
chunk and one decode step of the dense cell), and the xplane reader on a
trace recorded here."""
import json
import os

import numpy as np
import pytest

from bench import roofline as R, spec, trace as T
from bench.context import Run
from bench.stats import Window
from bench.window import SPAN_READ, SPAN_STEP, SPAN_SUBMIT, SPAN_WAIT, \
    StepRecord

SPANS = (SPAN_SUBMIT, SPAN_STEP, SPAN_READ, SPAN_WAIT)
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_v5e_decode.json")
DEV = "/device:TPU:0"


@pytest.fixture(scope="module")
def events():
    with open(DATA) as f:
        doc = json.load(f)
    return [T.Event(p, ln, n, float(s), float(d), n)
            for p, ln, n, s, d in doc["events"]]


def _brute_busy(events, w0, w1):
    """Busy time by marking every nanosecond bin (slow and plain)."""
    n = int(w1 - w0) // 1000 + 1
    mark = np.zeros(n, bool)
    for e in events:
        if e.plane == DEV and e.line == T.OPS_LINE:
            a = int(max(e.start_ns, w0) - w0) // 1000
            b = int(min(e.end_ns, w1) - w0 + 999) // 1000
            mark[a:b] = True
    return mark.sum() * 1e-6


def test_window_busy_and_idle(events):
    s = T.summarize(events, SPANS)
    host = [e for e in events if e.name == SPAN_STEP][0]
    assert s.window_ns == (host.start_ns, host.end_ns)
    assert s.devices == [DEV]
    assert s.busy_s == pytest.approx(_brute_busy(events, *s.window_ns),
                                     rel=2e-3)
    assert 0.0 < s.idle_share < 0.1          # this stretch is all device
    assert s.busy_s + sum(e - a for a, e, _ in s.gaps) * 1e-9 == \
        pytest.approx(s.window_s)


def test_program_executions(events):
    s = T.summarize(events, SPANS)
    secs, n = s.module_seconds("jit_step")
    assert n == 1 and secs == pytest.approx(0.151914785)
    secs, n = s.module_seconds("jit_prefill")
    assert n == 1 and secs == pytest.approx(0.117193005)


def test_kernel_sums_and_self_time(events):
    s = T.summarize(events, SPANS)
    run = Run(config={}, traffic={}, window=None, steps=[], traced=(0, 0),
              summary=s, counters={}, dims=None, peaks=None)
    secs, n = run.op_seconds(r"^%paged_attn_pallas\.")
    assert n == 24                           # one per layer
    want = sum(e.dur_ns for e in events
               if e.name.startswith("%paged_attn_pallas.")) * 1e-9
    assert secs == pytest.approx(want)
    selfs = T.self_times(s.ops)
    # nesting: a loop's self time leaves out its body, so self times add
    # up to the busy time of the one line
    assert sum(t for _, t in selfs) * 1e-9 == pytest.approx(s.busy_s,
                                                            rel=1e-2)
    loops = [t for e, t in selfs if e.name.startswith("%while")]
    assert loops and all(t >= 0 for t in loops)
    top = s.top_ops(10)
    assert len(top) == 10 and top[0][1] >= top[-1][1]
    assert not top[0][0].startswith("%while")


def test_gaps_are_attributed_to_the_host_span_around_them(events):
    s0 = T.summarize(events, SPANS)
    a, b, _ = max(s0.gaps, key=lambda g: g[1] - g[0])
    host = [e for e in events if e.name == SPAN_STEP][0]
    # split the host's step span around the longest gap: the harness was
    # reading tokens then
    extra = [T.Event("/host:CPU", "python", SPAN_STEP, host.start_ns,
                     a - host.start_ns - 1),
             T.Event("/host:CPU", "python", SPAN_READ, a - 1, b - a + 2),
             T.Event("/host:CPU", "python", SPAN_STEP, b + 1,
                     host.end_ns - b - 1)]
    evs = [e for e in events if e.name != SPAN_STEP] + extra
    s = T.summarize(evs, SPANS)
    by = dict(s.idle_by_span())
    assert by[SPAN_READ] == pytest.approx((b - a) * 1e-9)
    assert sum(by.values()) == pytest.approx(
        sum(e - a for a, e, _ in s.gaps) * 1e-9)


def test_paged_attention_roofline_reader(events):
    """The reader's share, worked by hand for one decode step of 48 rows
    with 40 keys each."""
    c = spec.load_json(os.path.join(spec.BENCH, "configs",
                                    "qwen1.5-0.5b.json"))
    dims = spec.arch_module(c).dims(c)
    s = T.summarize(events, SPANS)
    step = StepRecord(0.0, 1.0, decode_ctx=[40] * 48, chunks=[(0, 32)])
    run = Run(config=c, traffic={}, window=Window(0, 1, [], 0),
              steps=[step], traced=(0, 1), summary=s, counters={},
              dims=dims, peaks=R.peaks("TPU v5 lite"))
    read = spec.metric_reader("paged_attn_roofline")
    flops = 4 * 16 * 64 * 40 * 48
    nbytes = 2 * 16 * 64 * 4 * 40 * 48 + 2 * 48 * 16 * 64 * 4
    need = 24 * max(flops / 197e12, nbytes / 819e9)
    secs, _ = run.op_seconds(r"^%paged_attn_pallas\.")
    assert read(run) == pytest.approx(100 * need / secs)
    assert 0 < read(run) < 100
    # one decode step, one jit_step: the step's time and share of peak
    assert spec.metric_reader("decode_step_ms")(run) == \
        pytest.approx(151.914785)
    mfu = spec.metric_reader("decode_mfu")(run)
    assert mfu == pytest.approx(100 * R.decode_step_flops(dims, [40] * 48)
                                / (0.151914785 * 197e12))
    pf = spec.metric_reader("prefill_mfu.batch")(run)
    assert pf == pytest.approx(100 * R.prefill_chunk_flops(dims, 0, 32)
                               / (0.117193005 * 197e12))
    # two decode steps recorded but one traced: nothing to read, not 0
    run.steps, run.traced = [step, step], (0, 2)
    assert read(run) is None
    assert spec.metric_reader("decode_mfu")(run) is None


def test_load_xplane_reads_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(SPAN_STEP):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation(SPAN_READ):
        pass
    jax.profiler.stop_trace()
    ev = T.load_xplane(T.find_xplane(str(tmp_path)), SPANS)
    names = {e.name for e in ev if not e.plane.startswith("/device:")}
    assert names == {SPAN_STEP, SPAN_READ}
    s = T.summarize(ev, SPANS)
    assert s.window_s > 0 and s.busy_s == 0.0     # no device on the CPU


def test_load_xplane_reads_every_host_span(tmp_path):
    """With no names given, the reader keeps every host event: the
    program's spans beside the harness's."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(SPAN_STEP):
        with jax.profiler.TraceAnnotation("serve.step", step=3):
            with jax.profiler.TraceAnnotation("serve.dispatch"):
                pass
    jax.profiler.stop_trace()
    ev = T.load_xplane(T.find_xplane(str(tmp_path)))
    names = {e.name for e in ev if not e.plane.startswith("/device:")}
    assert {SPAN_STEP, "serve.step", "serve.dispatch"} <= names
    run = Run(config={}, traffic={}, window=None, steps=[], traced=(0, 0),
              summary=T.summarize(ev, SPANS), counters={}, dims=None,
              peaks=None, events=ev)
    got = run.spans("serve.dispatch", "serve.step")
    assert [e.name for e in got] == ["serve.step", "serve.dispatch"]
