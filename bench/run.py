#!/usr/bin/env python3
"""One run of one benchmark cell on the machine it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (counted in ``setup_s``): weights from the seed, made on the
device in one jitted call; the engine the cell's files describe; a
warm-up that compiles every program the window uses (or loads it from
the persistent compile cache in the checkout).  Then the window: the
cell's traffic for ``--seconds``, driven from this one thread.  Then the
check: a sample of what the window served against the plain reference
(the cell's architecture module, ``bench/arch``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
a few-second sub-window with the profiler and prints the per-layer
metrics, read by ``bench/metrics/<name>.py`` from the trace's device ops,
its host spans (the harness's and the program's) and the engine's
counters.  The last line of standard output is one JSON object; the last
lines of standard error are the numbers compared, each beside its limit.
Off a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import jax  # noqa: E402

from bench import check, program, reference, roofline, spec  # noqa: E402
from bench import weights  # noqa: E402
from bench import stats as S  # noqa: E402
from bench import traffic as T  # noqa: E402
from bench.context import Run  # noqa: E402
from bench.trace import find_xplane, load_xplane, summarize  # noqa: E402
from bench.window import (Driver, Trace, SPAN_READ, SPAN_STEP,  # noqa: E402
                          SPAN_SUBMIT, SPAN_WAIT)

TRACE_SECONDS = 3.0
SPANS = (SPAN_SUBMIT, SPAN_STEP, SPAN_READ, SPAN_WAIT)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _sample(rng, items, k, key):
    """k items drawn from the seed, always including the largest by
    ``key``."""
    if len(items) <= k:
        return list(items)
    top = max(items, key=key)
    rest = [x for x in items if x is not top]
    pick = rng.choice(len(rest), size=k - 1, replace=False)
    return [top] + [rest[i] for i in sorted(pick)]


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, engine_hook=None,
             compile_cache: bool = True, control: bool = False) -> dict:
    """Run one cell; returns the result object.  Raises ``SystemExit`` off
    a TPU when ``require_tpu``.  ``engine_hook(engine)`` may break the
    engine before the warm-up (the fault tests); ``control`` adds the
    control's numbers on the same sample under ``control`` and its verdict
    under the cell's limits under ``control_correct`` (never in a benchmark
    run)."""
    cache_dir = None
    if compile_cache:
        from repro.launch.env import use_compile_cache
        cache_dir = use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
            f"{dev.platform!r} device(s)")
        raise SystemExit(1)
    c, mix, arch = cell.config, cell.traffic, cell.arch
    jax.config.update("jax_default_matmul_precision", c["matmul_precision"])
    log(f"cell {cell.name}: {c['name']} x {mix['loop']} loop; seed {seed}; "
        f"{dev.platform} {dev.device_kind} x{len(devices)}; compile cache "
        f"{cache_dir}")
    with program.CompileCounter() as compiles:
        params, engine, driver, requests, setup_s = _set_up(
            arch, c, mix, seed, seconds, engine_hook, compiles)
        win, tr, log_dir = _window(engine, driver, seconds, trace,
                                   compiles)
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    sample = _served_sample(getattr(arch, "cache_of", program.cache_of),
                            mix, seed, requests, win, engine)
    steps = driver.steps
    traced = (tr.step_lo, tr.step_hi) if tr else (-1, -1)
    counters = driver.counters
    del driver, engine                     # frees the pool before the check
    gc.collect()

    numbers, ctl_numbers = _check(arch, c, mix, params, sample, control)
    unread = () if any(s.kv is not None for s in sample) else \
        ("kv_rel_err",)
    correct, table = check.verdict(numbers, c["check"], unread)
    if ctl_numbers is not None:
        ctl_correct, ctl_table = check.verdict(ctl_numbers, c["check"],
                                               unread)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(win.records),
              "failed": sum(1 for r in win.records if r.rejected)}
    if not trace:
        e2e = S.end_to_end(win, [m["name"] for m in cell.end_to_end])
        e2e["setup_s"] = setup_s
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in e2e}
        result["device"] = device
    else:
        try:
            events = load_xplane(find_xplane(log_dir))
            summary = summarize(events, SPANS)
            run = Run(config=c, traffic=mix, window=win, steps=steps,
                      traced=traced, summary=summary, counters=counters,
                      dims=arch.dims(c),
                      peaks=(roofline.peaks(dev.device_kind) if require_tpu
                             else None),
                      events=[e for e in events
                              if not e.plane.startswith("/device:")])
            readers = spec.readers(cell.per_layer)
            got = {m["name"]: (readers[m["name"]](run), m["unit"])
                   for m in cell.per_layer}
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        result["metrics"] = {n: {"value": float(v), "unit": u}
                             for n, (v, u) in got.items() if v is not None}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_by_span(10)}
        log(f"trace: {summary.window_s:.3f} s window, busy "
            f"{summary.busy_s:.3f} s, {len(summary.ops)} device ops, "
            f"{len(summary.modules)} program executions, steps "
            f"[{traced[0]}, {traced[1]}); starting the trace stalled the "
            f"window {tr.start_cost_s:.3f} s")
    if ctl_numbers is not None:
        result["control"] = ctl_numbers
        result["control_correct"] = bool(ctl_correct)
        for name, row in ctl_table.items():
            log(f"control {name} = {row['value']!r} limit {row['limit']!r}")
        log(f"control correct: {ctl_correct}")
    result["checks"] = table
    for name, row in table.items():
        log(f"check {name} = {row['value']!r} limit {row['limit']!r}")
    return result


def _set_up(arch, c, mix, seed, seconds, engine_hook, compiles):
    """Weights, the engine, its warm-up and the requests: everything
    ``setup_s`` counts."""
    marks = [("start", T_PROCESS), ("jax", time.perf_counter())]
    params = jax.block_until_ready(
        weights.make_params(arch, c, weights.weight_seed(c, seed)))
    marks.append(("weights", time.perf_counter()))
    served, cfg, info = program.serving_params(c, params)
    marks.append(("encoded", time.perf_counter()))
    engine = program.make_engine(served, cfg, mix["engine"])
    if engine_hook is not None:
        engine_hook(engine)
    marks.append(("engine", time.perf_counter()))
    program.warm_up(engine, mix["engine"], seed, int(c["vocab_size"]))
    marks.append(("warm-up", time.perf_counter()))
    requests = T.make_requests(mix, seed, seconds, int(c["vocab_size"]))
    driver = Driver(engine, requests, mix["loop"])
    if mix["loop"] == "closed":
        ramp_steps = driver.ramp()
        marks.append((f"ramp ({ramp_steps} steps)", time.perf_counter()))
    setup_s = time.perf_counter() - T_PROCESS
    phases = ", ".join(f"{b[0]} {b[1] - a[1]:.2f}"
                       for a, b in zip(marks, marks[1:]))
    bundle = ("none" if not c.get("encoded") else
              "loaded" if info.get("loaded") else "built")
    log(f"set-up {setup_s:.3f} s ({phases}; encoded bundle {bundle}); "
        f"{compiles.n} programs built or loaded")
    return params, engine, driver, requests, setup_s


def _window(engine, driver, seconds, trace, compiles):
    """The measured window, with the profiler over its last seconds when
    ``trace``.  Reports compiles inside the window (there should be
    none)."""
    tr, log_dir = None, None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        tr = Trace(at_s=max(0.0, seconds - TRACE_SECONDS),
                   seconds=min(TRACE_SECONDS, seconds),
                   start=lambda: jax.profiler.start_trace(
                       log_dir, profiler_options=opts),
                   stop=jax.profiler.stop_trace)
    jit0, comp0 = engine.stats()["jit_compiles"], compiles.n
    m0 = dict(engine.metrics)
    win = driver.run(seconds, trace=tr)
    m1 = dict(engine.metrics)
    driver.counters = {k: m1[k] - m0[k] for k in m1}
    log(f"window {win.seconds:.3f} s: {len(win.records)} requests "
        f"submitted, {driver.rejects} rejected, "
        f"{driver.counters['steps']} engine steps; jit_compiles delta "
        f"{engine.stats()['jit_compiles'] - jit0}, programs built or "
        f"loaded in the window {compiles.n - comp0}")
    return win, tr, log_dir


def _served_sample(cache_of, mix, seed, requests, win, engine):
    """What the window served, drawn from the seed with the longest
    included: finished requests, and requests in flight with the K and V
    the cache holds for them (``cache_of``)."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 99])
    prompt = {r.index: r.prompt for r in requests}
    reqs = engine.requests
    recs = [r for r in win.records if r.rid is not None]
    finished = [r for r in recs if r.finished]
    flight = [r for r in recs if not r.finished
              and reqs[r.rid].state == "decoding" and reqs[r.rid].out]
    k_fin, k_fly = int(mix["sample"]["finished"]), \
        int(mix["sample"]["in_flight"])
    sample = [check.Served(prompt[r.index], list(reqs[r.rid].out))
              for r in _sample(rng, finished, k_fin,
                               key=lambda r: r.plen + len(r.tokens))]
    fly = _sample(rng, flight, k_fly, key=lambda r: reqs[r.rid].n_cached)
    if fly:
        ps = int(mix["engine"]["page_size"])
        n_pages = -(-max(reqs[r.rid].n_cached for r in fly) // ps)
        sample += [check.Served(prompt[r.index], list(reqs[r.rid].out),
                                kv=cache_of(engine, reqs[r.rid], n_pages))
                   for r in fly]
    return sample


def _check(arch, c, mix, params, sample, control):
    """The program's numbers over the sample against the reference of the
    cell's architecture, and (``control``) the control's."""
    if not sample:
        return {}, None
    amax = None
    if c.get("encoded"):
        e = c["encoded"]
        amax = arch.calibrate(
            c, params, seq_len=int(e["calib_seq"]), seed=int(e["calib_seed"]),
            n_batches=int(e["calib_batches"]),
            batch_size=int(e["calib_batch_size"]))
    bits = int((c.get("encoded") or {}).get("bits", 0))
    ref = arch.Reference(c, params, bits=bits, amax=amax)
    length = reference.padded_length(
        int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"]))
    t0 = time.perf_counter()
    numbers = check.compare(ref, sample, length)
    log(f"check: {len(sample)} requests, "
        f"{sum(len(s.out) for s in sample)} served tokens, "
        f"{sum(1 for s in sample if s.kv is not None)} in flight; "
        f"reference {time.perf_counter() - t0:.1f} s")
    if "kv_rel_err_by_layer" in numbers:
        log("kv_rel_err by layer: " + " ".join(
            f"{x:.3g}" for x in numbers["kv_rel_err_by_layer"]))
    if not control:
        return numbers, None
    ctl = arch.Reference(c, params, mode=c["control"]["mode"],
                         bits=int(c["control"]["bits"]), amax=amax)
    return numbers, check.control(ref, ctl, sample, length)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"the repro package is not under {ROOT}/src")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cell = spec.load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except SystemExit as e:
        return int(e.code or 1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
