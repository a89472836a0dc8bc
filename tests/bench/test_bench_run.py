"""The harness end to end at a tiny size on the CPU: it runs, its check
passes on the sound program, and it comes out not correct when the timed
path is broken underneath or when the control takes the program's
place.  Off a TPU the command itself exits non-zero and prints nothing."""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, run
from conftest import ROOT

LIMITS = {"token_gap": 1e-3, "kv_rel_err": 4e-6}
SECONDS = 1.0
SEED = 2 ** 33 + 77


@pytest.fixture(autouse=True)
def _bundles(tmp_path, monkeypatch):
    monkeypatch.setattr(program, "BUNDLE_DIR", str(tmp_path / "bundles"))


def _run(cell, **kw):
    return run.run_cell(cell, SEED, SECONDS, False, require_tpu=False,
                        compile_cache=False, **kw)


def _alter_tokens(engine):
    orig = engine._step

    def step(params, layers, tokens, pages, lens):
        toks, layers = orig(params, layers, tokens, pages, lens)
        return (toks + 1) % engine.cfg.vocab_size, layers
    engine._step = step


def _keep_state(engine):
    """The decode step returns the page pools it was given, unchanged."""
    orig = engine._step

    def step(params, layers, tokens, pages, lens):
        toks, _ = orig(params, jax.tree_util.tree_map(jnp.copy, layers),
                       tokens, pages, lens)
        return toks, layers
    engine._step = step


@pytest.mark.parametrize("encoded", [False, True], ids=["dense", "encoded"])
def test_sound_program_is_correct(tiny_cell, encoded):
    res = _run(tiny_cell(encoded=encoded, limits=LIMITS))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(LIMITS)
    assert res["checks"]["kv_rel_err"]["value"] is not None
    m = res["metrics"]
    assert {"output_tokens_per_s", "itl_p95_ms", "setup_s"} <= set(m)
    assert m["output_tokens_per_s"]["unit"] == "tokens/s"
    assert res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", [_alter_tokens, _keep_state],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(tiny_cell, fault):
    res = _run(tiny_cell(limits=LIMITS), engine_hook=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("encoded,limits", [
    (False, LIMITS), (True, LIMITS), (True, None)],
    ids=["dense", "encoded", "encoded-config-limits"])
def test_control_fails_the_limits(tiny_cell, encoded, limits):
    """The reference at the precision below the configuration's (three
    bf16 passes for float32 at highest; int4 codes for int8) in the
    program's place comes out not correct under the harness's own verdict,
    where the program comes out correct: under this size's limits, and for
    the encoded configuration under its file's own limits too.  (At this
    size three bf16 passes read a ``kv_rel_err`` of about 2e-5, under the
    dense file's 3e-4, which was set from the full size on the chip.)"""
    res = _run(tiny_cell(encoded=encoded, limits=limits), control=True)
    prog = {k: v["value"] for k, v in res["checks"].items()}
    ctl = res["control"]
    assert res["correct"]
    assert res["control_correct"] is False, (prog, ctl)
    assert list(res)[-1] == "checks"
    assert ctl["kv_rel_err"] > 10 * prog["kv_rel_err"]


def test_closed_loop_ramp_drains_the_first_prefill_wave(tiny_cell):
    """In a closed loop, set-up steps until every admitted request has its
    first token, so the window opens on decode."""
    from bench import traffic, weights
    from bench.window import Driver
    cell = tiny_cell()
    c, mix = cell.config, cell.traffic
    served, cfg, _ = program.serving_params(
        c, weights.make_params(cell.arch, c, weights.weight_seed(c, SEED)))
    engine = program.make_engine(served, cfg, mix["engine"])
    reqs = traffic.make_requests(mix, SEED, SECONDS, int(c["vocab_size"]))
    driver = Driver(engine, reqs, mix["loop"])
    steps = driver.ramp()
    assert steps >= 2                    # prompts of 20-40 tokens: 2 chunks
    assert not engine.sched.prefilling()
    live = [engine.requests[r.rid] for r in driver.records]
    assert len(live) == mix["streams"]
    assert all(r.state == "decoding" and r.out for r in live)
    win = driver.run(SECONDS)
    assert win.prefill_tokens == 0 or len(win.records) > mix["streams"]


def test_open_loop_runs_and_reports_queueing(tiny_cell):
    """An open-loop mix with the harness's host-side queueing readers (no
    open-loop cell is in BENCHMARK.json yet; a later one adds them by
    name)."""
    extra = [{"name": n, "unit": "ms", "moves": "ttft_p95_ms"}
             for n in ("gen_lag_ms_p95", "queue_wait_ms_p95")]
    res = run.run_cell(tiny_cell(loop="open", limits=LIMITS,
                                 extra_layer=extra), SEED, SECONDS,
                       True, require_tpu=False, compile_cache=False)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # host-side per-layer metrics read without a device trace; device
    # metrics find nothing on the CPU and are left out, never 0
    assert m["gen_lag_ms_p95"]["value"] >= 0
    assert m["queue_wait_ms_p95"]["value"] >= 0
    assert "paged_attn_roofline" not in m and "decode_mfu" not in m
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_traced_run_reads_the_programs_host_spans(tiny_cell):
    """A traced run hands the readers the program's ``serve.*`` spans: the
    host metrics read a number (on the CPU too: they come from host
    spans), and the trace's directory is gone once they have."""
    import tempfile
    before = set(os.listdir(tempfile.gettempdir()))
    res = run.run_cell(tiny_cell(limits=LIMITS), SEED, SECONDS, True,
                       require_tpu=False, compile_cache=False)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 < m["scheduler_host_ms"] < m["engine_host_ms"]
    assert 0 < m["dispatch_host_ms"] < m["engine_host_ms"]
    left = set(os.listdir(tempfile.gettempdir())) - before
    assert not [d for d in left if d.startswith("bench-trace-")]


def _bench_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dense-decode-batch",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_exits_nonzero_off_a_tpu():
    p = _bench_cmd(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in ("bench", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d)
    p = _bench_cmd(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_sample_includes_the_longest():
    rng = np.random.default_rng(0)
    items = list(range(100))
    got = run._sample(rng, items, 5, key=lambda x: x)
    assert len(got) == 5 and 99 in got and len(set(got)) == 5
    assert run._sample(rng, [1, 2], 5, key=lambda x: x) == [1, 2]

