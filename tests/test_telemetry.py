"""Observability subsystem (DESIGN.md §9): the shared percentile helper,
the metrics registry, the span API with its two sinks (the profiler's
trace always, Chrome trace events when enabled; disabled path), the
engine's trace/metrics wiring (``serve.*`` span names and nesting in both
sinks, phase/latency reconciliation, in-flight TTFT), and drift-monitor
parity with the offline logit-agreement measurement."""
import glob
import json
import re

import numpy as np
import jax
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.obs import (percentile, percentiles, Counter, Gauge, Histogram,
                       MetricsRegistry, Tracer, DriftMonitor,
                       logit_agreement)
from repro.serve import Engine
from repro.serve.scheduler import Request, FINISHED, DECODING
from repro.serve.telemetry import ServeTelemetry, req_tid, TID_ENGINE


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# percentile: the one repo-wide implementation
# ---------------------------------------------------------------------------

def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 7, 100):
        xs = rng.normal(size=n).tolist()
        for q in (0, 1, 25, 50, 90, 95, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), rel=1e-12, abs=1e-12)
    assert np.isnan(percentile([], 50))
    assert percentiles([1.0, 2.0], (0, 100)) == {0: 1.0, 100: 2.0}
    with pytest.raises(ValueError):
        percentile([1.0], 101)
    with pytest.raises(ValueError):
        percentile([1.0], -1)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_labels():
    reg = MetricsRegistry()
    c = reg.counter("toks", "tokens")
    c.inc(3, mac="fp")
    c.inc(2, mac="encoded")
    c.inc()                                     # unlabeled series
    assert c.value(mac="fp") == 3
    assert c.value(mac="encoded") == 2
    assert c.total() == 6
    assert reg.counter("toks") is c             # get-or-create
    with pytest.raises(ValueError):
        c.inc(-1)                               # counters only go up
    g = reg.gauge("depth")
    g.set(4)
    g.inc(2)
    assert g.value() == 6
    assert np.isnan(g.value(mac="fp"))          # unset series
    with pytest.raises(TypeError):
        reg.gauge("toks")                       # kind conflict


def test_histogram_exact_percentiles_and_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1, 5, 10))
    xs = [0.5, 2, 3, 7, 12, 40]
    for v in xs:
        h.observe(v, mac="fp")
    assert h.count(mac="fp") == len(xs)
    # exact order statistics over the raw samples, not bucket bounds
    assert h.percentile(50, mac="fp") == pytest.approx(
        float(np.percentile(xs, 50)))
    s = h.summary(mac="fp")
    assert s["min"] == 0.5 and s["max"] == 40
    assert s["buckets"] == {"1": 1, "5": 2, "10": 1, "+Inf": 2}
    assert sum(s["buckets"].values()) == s["count"]
    assert h.count(mac="encoded") == 0
    assert np.isnan(h.percentile(50, mac="encoded"))


def test_registry_snapshot_schema(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a", "ca").inc(1, mac="fp")
    reg.gauge("b").set(2)
    reg.histogram("c").observe(0.01)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["a"]["series"] == {"mac=fp": 1.0}
    assert snap["gauges"]["b"]["series"] == {"": 2.0}
    assert snap["histograms"]["c"]["series"][""]["count"] == 1
    p = tmp_path / "m.json"
    reg.write_json(str(p))
    assert json.loads(p.read_text()) == json.loads(json.dumps(
        snap, default=float))


# ---------------------------------------------------------------------------
# tracer: Chrome trace-event schema + disabled fast path
# ---------------------------------------------------------------------------

def test_tracer_chrome_schema(tmp_path):
    tr = Tracer(enabled=True)
    tr.thread(0, "engine")
    tr.thread(7, "req 7")
    with tr.span("outer", tid=0, cat="engine", args={"k": 1}):
        with tr.span("inner", tid=0):
            pass
    t0 = tr.now()
    tr.complete("manual", t0, tr.now(), tid=7)
    tr.instant("evict", tid=7, args={"rid": 7})
    ev = tr.chrome_events()
    meta = [e for e in ev if e["ph"] == "M"]
    assert [m["args"]["name"] for m in meta] == ["engine", "req 7"]
    spans = [e for e in ev if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["inner", "outer", "manual"]
    for e in spans:                      # complete events: begin/end match
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0
    # nesting: inner lies within outer on the same track
    inner, outer = spans[0], spans[1]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    inst = [e for e in ev if e["ph"] == "i"]
    assert inst[0]["name"] == "evict" and inst[0]["s"] == "t"
    # exports: object form (Perfetto) and JSONL both round-trip
    pc, pl = tmp_path / "t.json", tmp_path / "t.jsonl"
    tr.write_chrome(str(pc))
    tr.write_jsonl(str(pl))
    doc = json.loads(pc.read_text())
    assert doc["traceEvents"] == json.loads(json.dumps(ev, default=float))
    lines = [json.loads(l) for l in pl.read_text().splitlines()]
    assert lines == doc["traceEvents"]


def test_disabled_tracer_is_free():
    tr = Tracer(enabled=False)
    # span() hands back the profiler's annotation alone (what a span costs
    # with the Chrome sink off), and records no Chrome event
    s1, s2 = tr.span("a", tid=3), tr.span("b", args={"x": 1})
    assert type(s1) is jax.profiler.TraceAnnotation
    assert type(s2) is jax.profiler.TraceAnnotation
    with s1:
        pass
    with s2 as sp:
        sp.set_metadata(y=2)
    tr.thread(0, "engine")
    tr.complete("c", 0.0, 1.0)
    tr.instant("d")
    assert tr.events == [] and tr.chrome_events() == []


def test_serve_telemetry_bundle(tmp_path):
    tel = ServeTelemetry.disabled()
    assert not tel.tracer.enabled and tel.drift is None
    tel.write()                                  # all-None export: no-op
    tel = ServeTelemetry(trace=True)
    ev = tel.tracer.chrome_events()
    assert {e["tid"] for e in ev} == {TID_ENGINE}
    assert req_tid(0) > TID_ENGINE               # request tracks don't clash
    p = tmp_path / "t.json"
    tel.write(trace_out=str(p))
    assert "traceEvents" in json.loads(p.read_text())


# ---------------------------------------------------------------------------
# engine wiring: spans, reconciliation, stats
# ---------------------------------------------------------------------------

SERVE_SPANS = {"serve.step", "serve.admit", "serve.prefill_chunk",
               "serve.grow", "serve.inputs", "serve.decode",
               "serve.dispatch", "serve.wait", "serve.commit",
               "serve.gauges"}
# the children of each span, in order, as a regular expression over their
# names less the ``serve.`` prefix; a span not listed has no children
CHILDREN = {
    "step": r"admit ((prefill_chunk )+admit )*grow "
            r"(inputs (decode |draft verify )commit )?gauges ",
    "prefill_chunk": r"inputs dispatch (wait )?",
    "decode": r"dispatch wait ",
    "draft": r"dispatch ",
    "verify": r"dispatch wait ",
}


def _chrome_spans(events):
    """(name, start, end) of the engine track's ``serve.*`` spans."""
    return [(e["name"], e["ts"], e["ts"] + e["dur"]) for e in events
            if e["ph"] == "X" and e["name"].startswith("serve.")
            and e["tid"] == TID_ENGINE]


def _xplane_spans(log_dir):
    """(name, start, end) of the ``serve.*`` host events of the one
    profiler trace under ``log_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")]


def _span_tree(spans):
    """Nest (name, start, end) spans of one thread by containment:
    [(name, children)] in start order."""
    roots, stack = [], []
    for name, t0, t1 in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= t0:
            stack.pop()
        if stack:
            assert t1 <= stack[-1][2], f"{name} overlaps {stack[-1][0]}"
        node = (name, [])
        (stack[-1][1][1] if stack else roots).append(node)
        stack.append((name, node, t1))
    return roots


def _check_step_tree(spans):
    """Assert that every span is under a ``serve.step`` and that each
    span's children follow ``CHILDREN``; returns the tree as nested
    tuples."""
    def walk(node):
        name, kids = node
        seq = "".join(k[0].removeprefix("serve.") + " " for k in kids)
        want = CHILDREN.get(name.removeprefix("serve."), "")
        assert re.fullmatch(want, seq), (name, seq)
        return name, tuple(walk(k) for k in kids)
    tree = _span_tree(spans)
    assert tree and all(name == "serve.step" for name, _ in tree)
    return tuple(walk(n) for n in tree)


def test_engine_spans_reach_the_profiler(qwen, tmp_path):
    """One span API, two sinks.  With the Chrome tracer off the engine's
    spans still reach the profiler's trace: one ``serve.step`` per engine
    step with its children nested inside it in order, and no Chrome event
    recorded.  With the tracer on, the profiler and the Chrome export get
    the same names in the same nesting."""
    cfg, params = qwen
    off, on = tmp_path / "off", tmp_path / "on"
    tel, eng, _ = _pressure_run(params, cfg, trace=False,
                                profile_dir=str(off))
    assert tel.tracer.events == [] and tel.tracer.chrome_events() == []
    tree = _check_step_tree(_xplane_spans(off))
    assert len(tree) == eng.stats()["steps"]
    flat = str(tree)
    assert "serve.prefill_chunk" in flat and "serve.decode" in flat
    assert "('serve.wait', ())" in flat
    tel, eng, _ = _pressure_run(params, cfg, trace=True, profile_dir=str(on))
    assert _check_step_tree(_xplane_spans(on)) == tree
    assert _check_step_tree(_chrome_spans(tel.tracer.chrome_events())) == \
        tree


def test_step_span_args(qwen):
    """``serve.step`` carries its index and what it did, the chunk its
    request and range, in the Chrome sink (the profiler's gets the same
    metadata)."""
    cfg, params = qwen
    tel, eng, _ = _pressure_run(params, cfg)
    ev = [e for e in tel.tracer.chrome_events() if e["ph"] == "X"]
    steps = [e["args"] for e in ev if e["name"] == "serve.step"]
    assert [a["step"] for a in steps] == list(range(1, len(steps) + 1))
    assert sum(a["n_chunks"] for a in steps) == \
        eng.stats()["prefill_chunks"]
    assert sum(a["n_active"] for a in steps) == \
        eng.stats()["decode_tokens"]
    chunks = [e["args"] for e in ev if e["name"] == "serve.prefill_chunk"]
    assert chunks and all(set(a) == {"rid", "start", "n"} for a in chunks)


def test_spec_round_spans(qwen):
    """A speculative round nests ``serve.draft`` (its dispatch) and
    ``serve.verify`` (its dispatch and the one wait for both token sets)
    where a plain step has ``serve.decode``."""
    cfg, params = qwen
    tel = ServeTelemetry(trace=True)
    eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=16,
                 prefill_chunk=4, spec_decode=2, telemetry=tel)
    rng = np.random.default_rng(1)
    for n in (5, 3):
        eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                   max_new=6)
    eng.run()
    tree = _check_step_tree(_chrome_spans(tel.tracer.chrome_events()))
    assert len(tree) == eng.stats()["steps"]
    assert "serve.verify" in str(tree) and "serve.decode" not in str(tree)


def _pressure_run(params, cfg, *, trace=True, profile_dir=None):
    """2 slots / 6×4-token pages / optimistic reserve: this geometry
    deterministically evicts AND page-stalls, so every lifecycle event
    kind lands in the trace."""
    tel = ServeTelemetry(trace=trace, profile_dir=profile_dir)
    eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=6,
                 reserve="optimistic", prefill_chunk=4, telemetry=tel)
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                       max_new=10) for n in (5, 3, 6)]
    eng.run()
    return tel, eng, rids


def test_engine_trace_lifecycle_and_reconciliation(qwen):
    cfg, params = qwen
    tel, eng, rids = _pressure_run(params, cfg)
    ev = tel.tracer.chrome_events()
    names = {e["name"] for e in ev}
    assert {"submit", "admit", "first_token", "evict", "stall", "request",
            "queued", "prefill", "decode"} | SERVE_SPANS <= names
    spans = [e for e in ev if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
    # engine-track step spans are sequential (the loop never overlaps),
    # one per engine step, and every serve.* span nests inside one
    steps = [e for e in spans if e["name"] == "serve.step"]
    ends = [s["ts"] + s["dur"] for s in steps]
    assert all(a["ts"] >= e - 1e-6 for a, e in zip(steps[1:], ends))
    tree = _check_step_tree(_chrome_spans(ev))
    assert len(tree) == len(steps) == eng.stats()["steps"]
    # phase spans telescope to the request span exactly (float rounding
    # only), for every request — including the evicted one
    for rid in rids:
        tid = req_tid(rid)
        mine = {e["name"]: e for e in spans if e["tid"] == tid}
        total = sum(mine[n]["dur"] for n in ("queued", "prefill", "decode"))
        assert total == pytest.approx(mine["request"]["dur"], abs=2.0)
        # ...and the request span is the stats() latency
        r = eng.requests[rid]
        assert mine["request"]["dur"] == pytest.approx(
            (r.t_finish - r.t_arrive) * 1e6, abs=2.0)
    st = eng.stats()
    assert st["evictions"] >= 1 and st["stalls"] >= 1
    assert st["finished"] == 3
    assert not any(k.startswith("device_") for k in st)
    # registry gauges settle to an idle pool
    reg = tel.registry
    assert reg.gauge("pages_held").value() == 0
    assert reg.gauge("queue_depth").value() == 0
    # first token per request comes from the prefill's last position, so
    # decode steps account for the remaining max_new - 1 each
    assert reg.counter("decode_tokens").value(mac=cfg.mac.mode) == 27


def test_engine_trace_and_metrics_read_back_from_disk(qwen, tmp_path):
    """What ``launch/serve.py --trace-out/--metrics-out`` writes is what a
    reader gets: the Chrome trace re-read from disk holds the lifecycle
    spans with phases telescoping to each request span, and the metrics
    snapshot holds the evictions counter and step-time histogram."""
    cfg, params = qwen
    tel, eng, rids = _pressure_run(params, cfg)
    trace, metrics = tmp_path / "trace.json", tmp_path / "metrics.json"
    tel.write(trace_out=str(trace), metrics_out=str(metrics))
    ev = json.loads(trace.read_text())["traceEvents"]
    assert {"serve.step", "serve.prefill_chunk", "serve.decode", "evict",
            "request", "queued", "prefill", "decode"} <= \
        {e["name"] for e in ev}
    spans = [e for e in ev if e.get("ph") == "X"]
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
    phases, reqs = {}, {}
    for e in spans:
        if e["name"] in ("queued", "prefill", "decode"):
            phases[e["tid"]] = phases.get(e["tid"], 0) + e["dur"]
        elif e["name"] == "request":
            reqs[e["tid"]] = e["dur"]
    assert sorted(reqs) == sorted(req_tid(r) for r in rids)
    for tid, dur in reqs.items():
        assert phases[tid] == pytest.approx(dur, abs=2.0)
    m = json.loads(metrics.read_text())
    assert m["counters"]["evictions"]["series"][""] >= 1
    assert m["histograms"]["step_ms"]["series"]


def test_tracing_does_not_change_tokens(qwen):
    cfg, params = qwen
    _, eng_on, rids_on = _pressure_run(params, cfg)
    eng_off = Engine(params, cfg, n_slots=2, page_size=4, n_pages=6,
                     reserve="optimistic", prefill_chunk=4)
    rng = np.random.default_rng(0)
    rids_off = [eng_off.submit(
        rng.integers(0, cfg.vocab_size, n).astype(np.int32), max_new=10)
        for n in (5, 3, 6)]
    eng_off.run()
    ron, roff = eng_on.results(), eng_off.results()
    assert all(ron[a].tolist() == roff[b].tolist()
               for a, b in zip(rids_on, rids_off))


def test_stats_ttft_includes_inflight_and_tpot(qwen):
    """TTFT must cover requests that produced a first token but have not
    finished (the old finished-only version under-reported under load);
    TPOT is (t_finish - t_first) / (n_out - 1) over finished requests."""
    cfg, params = qwen
    eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=32)
    done = Request(rid=0, prompt=np.zeros(4, np.int32), max_new=4)
    done.state, done.out = FINISHED, [1, 2, 3, 4]
    done.t_arrive, done.t_first, done.t_finish = 100.0, 101.0, 104.0
    flight = Request(rid=1, prompt=np.zeros(4, np.int32), max_new=4)
    flight.state, flight.out = DECODING, [1]
    flight.t_arrive, flight.t_first = 100.0, 109.0   # slow, still running
    eng.requests = {0: done, 1: flight}
    st = eng.stats()
    assert st["latency_p50_s"] == pytest.approx(4.0)   # finished only
    assert st["ttft_p99_s"] == pytest.approx(9.0 - 0.08)  # in-flight seen
    assert st["ttft_p50_s"] == pytest.approx(5.0)      # median of {1, 9}
    assert st["tpot_p50_s"] == pytest.approx(3.0 / 3)  # (104-101)/(4-1)


# ---------------------------------------------------------------------------
# drift monitor: online gauge == offline measurement, by construction
# ---------------------------------------------------------------------------

def test_drift_monitor_parity_with_offline(qwen):
    cfg, params = qwen
    # a perturbed copy stands in for the encoded parameter set
    params_b = jax.tree_util.tree_map(lambda a: a * 1.02, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 9)]
    reg = MetricsRegistry()
    mon = DriftMonitor(params, cfg, every=4).bind(reg)
    got = mon.sample(params_b, cfg, prompts)
    ref_top1, ref_delta = logit_agreement(params, cfg, params_b, cfg,
                                          prompts, max_len=mon.max_len)
    assert got == ref_top1
    assert reg.gauge("encoded_drift_top1").value() == ref_top1
    assert reg.gauge("encoded_drift_abs_logit").value() == ref_delta
    assert reg.counter("drift_samples").total() == 1
    # cadence: only every Nth step samples; identical params agree fully
    assert mon.maybe_sample(3, params, cfg, prompts) is None
    assert mon.maybe_sample(4, params, cfg, prompts) == 1.0
    assert mon.last == 1.0
    with pytest.raises(ValueError):
        DriftMonitor(params, cfg, every=0)


def test_drift_monitor_in_engine(qwen):
    cfg, params = qwen
    tel = ServeTelemetry(drift=DriftMonitor(params, cfg, every=1))
    eng = Engine(params, cfg, n_slots=1, page_size=4, n_pages=16,
                 telemetry=tel)
    eng.submit(np.arange(5, dtype=np.int32) % cfg.vocab_size, max_new=3)
    eng.run()
    st = eng.stats()
    # dense-vs-dense: the gauge must read exact agreement
    assert st["encoded_drift_top1"] == 1.0
    assert tel.registry.counter("drift_samples").total() >= 1
