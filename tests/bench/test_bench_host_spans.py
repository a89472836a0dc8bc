"""The reduction of the program's ``serve.*`` host spans
(``bench/host_spans.py``): on hand-made spans, and on a small trace
recorded on a TPU v5e (one prefill chunk and two decode steps of the
dense cell, with the harness's and the engine's spans)."""
import json
import os

import pytest

from bench import host_spans as H, roofline as R, spec, trace as T
from bench.context import Run
from bench.window import SPAN_READ, SPAN_STEP, SPAN_SUBMIT, SPAN_WAIT, \
    StepRecord

SPANS = (SPAN_SUBMIT, SPAN_STEP, SPAN_READ, SPAN_WAIT)
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_v5e_serve_spans.json")
HOST, DEV = "/host:CPU", "/device:TPU:0"


@pytest.fixture(scope="module")
def events():
    with open(DATA) as f:
        doc = json.load(f)
    return [T.Event(p, ln, n, float(s), float(d), n)
            for p, ln, n, s, d in doc["events"]]


def _ev(name, t0, t1, plane=HOST, line="python"):
    return T.Event(plane, line, name, float(t0), float(t1 - t0), name)


def _step(t0, *, chunk=False):
    """One engine step of hand-made spans starting at ``t0`` ns, 100 µs
    long: the harness's span, ``serve.step`` and its children."""
    k = 1000
    out = [_ev(SPAN_STEP, t0, t0 + 100 * k),
           _ev(H.STEP, t0 + k, t0 + 99 * k),
           _ev(H.ADMIT, t0 + 2 * k, t0 + 4 * k)]
    t = t0 + 4 * k
    if chunk:
        out += [_ev(H.CHUNK, t, t + 20 * k),
                _ev(H.INPUTS, t + k, t + 3 * k),
                _ev(H.DISPATCH, t + 3 * k, t + 6 * k),
                _ev(H.WAIT, t + 6 * k, t + 19 * k)]
        t += 20 * k
    out += [_ev(H.GROW, t, t + 5 * k), _ev(H.INPUTS, t + 5 * k, t + 9 * k),
            _ev(H.DECODE, t + 9 * k, t + 60 * k),
            _ev(H.DISPATCH, t + 9 * k, t + 12 * k),
            _ev(H.WAIT, t + 12 * k, t + 60 * k),
            _ev(H.COMMIT, t + 60 * k, t + 62 * k),
            _ev(H.GAUGES, t + 62 * k, t + 63 * k)]
    return out


def test_step_split_by_hand():
    evs = _step(0, chunk=True) + _step(200_000)
    notes = []
    got = H.step_split(evs, 2, notes.append)
    assert not notes
    # serve.step 98 µs less its waits (13 + 48 in the first, 48 in the
    # second); admit + grow 7 µs each; inputs + dispatch of the decode
    # only, 7 µs, the chunk's 5 µs left out
    assert got["engine_host_ms"] == pytest.approx((98 - 61 + 98 - 48) / 2e3)
    assert got["scheduler_host_ms"] == pytest.approx(7e-3)
    assert got["dispatch_host_ms"] == pytest.approx(7e-3)


def test_step_split_none_with_a_note():
    notes = []
    assert H.step_split(_step(0), 2, notes.append) is None  # count differs
    no_decode = [e for e in _step(0) if e.name != H.DECODE]
    assert H.step_split(no_decode, 1, notes.append) is None
    assert len(notes) == 2 and all("not read" in n for n in notes)


def test_innermost_idle_by_hand():
    """Idle from the device's last op of one step to its first of the
    next crosses the commit, the gauges, the harness's read and the next
    step's admission, growth, inputs and dispatch: each piece goes to the
    innermost span over it, and the pieces add up to the gap."""
    evs = _step(0) + _step(100_000)
    k = 1000
    # the device runs from 3 µs into each serve.wait to its end
    ops = [_ev("%fusion", t + 16 * k, t + 64 * k, plane=DEV,
               line=T.OPS_LINE) for t in (0, 100 * k)]
    summary = T.summarize(evs + ops, SPANS)
    spans = H.host_spans(evs, SPANS + H.SPANS)
    got = dict(H.innermost_idle(summary, spans))
    old = dict(summary.idle_by_span())
    assert sum(got.values()) == pytest.approx(sum(old.values()))
    assert set(old) == {SPAN_STEP}
    us = {n: s * 1e6 for n, s in got.items()}
    assert us == pytest.approx({
        H.ADMIT: 4, H.GROW: 10, H.INPUTS: 8, H.DISPATCH: 6, H.COMMIT: 4,
        H.GAUGES: 2, H.STEP: 1 + 32 + 1 + 32, SPAN_STEP: 4})


# ---------------------------------------------------------------------------
# the recorded trace
# ---------------------------------------------------------------------------

def _run(events):
    """A Run over the recorded trace reduced as the harness reduces it:
    the window and the busy time from the harness's spans alone."""
    with open(os.path.join(spec.ROOT, "bench", "configs",
                           "qwen1.5-0.5b.json")) as f:
        c = json.load(f)
    steps = [StepRecord(0.0, 0.0, [33], [(0, 32)]),
             StepRecord(0.0, 0.0, [34], [])]
    return Run(config=c, traffic={}, window=None, steps=steps,
               traced=(0, 2), summary=T.summarize(events, SPANS),
               counters={}, dims=spec.arch_module(c).dims(c),
               peaks=R.peaks("TPU v5 lite"),
               events=[e for e in events
                       if not e.plane.startswith("/device:")])


def test_recorded_spans_nest(events):
    top = [e for e in events if e.name == SPAN_STEP]
    found = H.steps(events)
    assert len(top) == len(found) == 2
    for outer, (s, inside) in zip(top, found):
        assert outer.start_ns <= s.start_ns and s.end_ns <= outer.end_ns
        names = [e.name for e in inside]
        assert names.count(H.DECODE) == 1 and names.count(H.WAIT) >= 1
    assert [e.name for e in found[0][1]].count(H.CHUNK) == 1
    assert H.CHUNK not in [e.name for e in found[1][1]]


def test_existing_metrics_read_the_same(events):
    """Loading the program's spans beside the harness's changes none of
    the numbers the accepted per-layer metrics read."""
    harness_only = [e for e in events if e.name not in H.SPANS]
    a, b = _run(events), _run(harness_only)
    assert a.summary.window_ns == b.summary.window_ns
    assert a.summary.busy_s == b.summary.busy_s
    assert a.summary.gaps == b.summary.gaps
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    readers = spec.readers([m for m in per_layer
                            if m["name"] != "encoded_matmul_roofline"])
    got = {n: (r(a), r(b)) for n, r in readers.items()}
    for n in ("device_idle_share", "decode_step_ms", "decode_mfu",
              "paged_attn_roofline"):
        assert got[n][0] is not None and got[n][0] == got[n][1], n


def test_recorded_idle_splits_engine_step(events):
    """What the harness's reduction files under ``engine.step`` splits by
    the program's spans, with the same total."""
    summary = _run(events).summary
    spans = H.host_spans(events, SPANS + H.SPANS)
    got = dict(H.innermost_idle(summary, spans))
    old = dict(summary.idle_by_span())
    assert sum(got.values()) == pytest.approx(sum(old.values()), rel=1e-12)
    inside = sum(s for n, s in got.items() if n in H.SPANS)
    assert inside > 0.9 * old[SPAN_STEP]
    assert got.get(SPAN_STEP, 0.0) + inside == \
        pytest.approx(old[SPAN_STEP], rel=0.05)


def test_recorded_step_split(events):
    found = H.steps(events)
    got = H.step_split(events, 2, pytest.fail)
    step_less_wait = [s.dur_ns - sum(e.dur_ns for e in inside
                                     if e.name == H.WAIT)
                      for s, inside in found]
    assert got["engine_host_ms"] == pytest.approx(
        sum(step_less_wait) / 2 * 1e-6)
    assert 0 < got["scheduler_host_ms"] < got["engine_host_ms"]
    assert 0 < got["dispatch_host_ms"] < got["engine_host_ms"]


def test_host_metric_readers_read_the_step_split(events):
    """The three host metrics read ``step_split`` through the run's span
    access; with the program's spans gone from the trace they read
    nothing, not 0."""
    want = H.step_split(events, 2, pytest.fail)
    run = _run(events)
    assert [e.name for e in run.spans(H.STEP)] == [H.STEP, H.STEP]
    for name in ("engine_host_ms", "dispatch_host_ms", "scheduler_host_ms"):
        assert spec.metric_reader(name)(run) == want[name]
    bare = _run([e for e in events if e.name not in H.SPANS])
    for name in ("engine_host_ms", "dispatch_host_ms", "scheduler_host_ms"):
        assert spec.metric_reader(name)(bare) is None
