"""The Mellum 2 configuration (``bench/configs/mellum2-12b-a2.5b-4L.json``)
and its architecture module (``bench/arch/Mellum2ForCausalLM.py``): the
weights' layout is the program's, the published sizes give the counts
the rooflines read, yarn is transformers' formula, the reference agrees
with the program served through the engine (chunked prefill, then decode
through the paged cache) at a small size on the CPU, and the expert
roofline reads a hand-computed share from a synthetic trace and nothing
where it has nothing to read."""
import copy
import dataclasses
import math
import os
import tempfile

import jax
import numpy as np
import pytest

from bench import check, program, roofline as R, run, spec, weights
from bench.context import Run
from bench.stats import Window
from bench.trace import Event, summarize
from bench.window import SPAN_STEP, StepRecord
from conftest import tiny_traffic

SEED = 2 ** 34 + 5
CONFIG = os.path.join(spec.BENCH, "configs", "mellum2-12b-a2.5b-4L.json")


def published():
    return spec.load_json(CONFIG)


def tiny_mellum() -> dict:
    """The file's format and layer pattern at toy widths: a 24-key window
    that requests outgrow, yarn over an original context of 32, 8
    experts of 32, top-2."""
    c = copy.deepcopy(published())
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, num_experts=8, num_experts_per_tok=2,
             moe_intermediate_size=32, vocab_size=512, sliding_window=24,
             max_position_embeddings=512, name="mellum-tiny")
    rp = c["rope_parameters"]
    rp["full_attention"].update(rope_theta=10000,
                                original_max_position_embeddings=32)
    rp["sliding_attention"].update(rope_theta=10000)
    c["program"].update(arch="mellum-tiny", d_model=64, n_heads=4,
                        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
                        n_experts=8, top_k=2, d_ff_expert=32,
                        sliding_window=24, rope_theta=10000.0,
                        rope_yarn_original_max=32)
    c["check"] = {"token_gap": 1e-4, "kv_rel_err": 1e-5}
    return c


MELLUM = spec.arch_module(published())


# ---------------------------------------------------------------------------
# the configuration and its sizes
# ---------------------------------------------------------------------------

def test_config_keeps_every_catalog_number():
    """Every top-level number of the catalog entry as published, but the
    depth (``reduced``); the layer pattern and rope groups whole."""
    c = published()
    assert set(c["reduced"]) == {"torch_dtype", "num_hidden_layers"}
    assert c["num_hidden_layers"] == 4 and len(c["layer_types"]) == 28
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["num_experts"],
            c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["vocab_size"], c["sliding_window"]) == \
        (2304, 32, 4, 128, 64, 8, 896, 98304, 1024)
    p = c["program"]
    assert p["global_layers"] == [3] and p["n_layers"] == 4
    cfg = program.model_config(c)
    assert cfg.layer_windows == [1024, 1024, 1024, None]
    assert MELLUM.layer_kinds(c) == ("sliding_attention",) * 3 + \
        ("full_attention",)


def test_dims_from_the_published_config():
    c = published()
    d = MELLUM.dims(c)
    assert (d.layers, d.heads, d.kv_heads, d.head_dim, d.kv_bytes) == \
        (4, 32, 4, 128, 4)
    attn = 2304 * 4096 * 2 + 2304 * 512 * 2          # q, o; k, v
    assert attn == 21_233_664
    per_layer = attn + 2304 * 64 + 8 * 3 * 2304 * 896
    assert d.matmul_params == 4 * per_layer + 2304 * 98304 == 510_197_760
    assert d.windows == (1024, 1024, 1024, None)
    # 48 rows at 3,000 keys: 1,024 in each window layer
    assert R.paged_attn_call(d, [3000] * 48)[1] == \
        2 * 4 * 128 * 4 * 3000 * 48 + 2 * 48 * 32 * 128 * 4
    assert R.paged_attn_step_s(d, [3000] * 48, 1e30, 1.0) == \
        R.paged_attn_call(d, [3000] * 48)[1] + \
        3 * R.paged_attn_call(d, [1024] * 48)[1]


def test_yarn_is_transformers_formula():
    """The full layers' frequencies written out per dimension as
    transformers' ``_compute_yarn_parameters`` computes them, and the
    program's own (``nn/common.yarn_inv_freq``) equal to them."""
    from repro.nn.common import yarn_inv_freq
    c = published()
    inv, att = MELLUM.yarn(c)
    base, dim, orig, factor = 500000.0, 128, 8192, 131072 / 8192
    assert factor == 16 and att == pytest.approx(0.1 * math.log(16) + 1)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / \
            (2 * math.log(base))
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), 127)
    for i in range(dim // 2):
        f = 1.0 / base ** (2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = f / factor * ramp + f * (1 - ramp)
        assert inv[i] == pytest.approx(want, rel=1e-6), i
    assert inv[0] == pytest.approx(1.0) and \
        inv[-1] == pytest.approx(base ** (-(dim - 2) / dim) / 16, rel=1e-6)
    prog = yarn_inv_freq(dim, base, 16.0, orig, 32.0, 1.0)
    np.testing.assert_array_equal(prog, inv)


# ---------------------------------------------------------------------------
# the reference against the program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    c = tiny_mellum()
    arch = spec.arch_module(c)
    return c, arch, weights.make_params(arch, c, SEED)


def test_weights_layout_is_the_programs(tiny):
    from repro.models import init_model
    c, _, params = tiny
    cfg = program.model_config(c)
    want = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    assert jax.tree_util.tree_structure(want) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype


@pytest.mark.parametrize("backend", ["auto", "pallas_interpret"])
def test_reference_matches_the_engine(tiny, backend):
    """Two requests through ``Engine``: prompts of 37 and 22 tokens in
    chunks of 16, then 20 tokens of decode through the paged cache, the
    window layers past their 24 keys.  Every served token is the
    reference's best to 1e-4 of a logit, and the K and V the pools hold
    are the reference's to 1e-5 of the larger of the leaf's norm and the
    median leaf's: float32 at full precision, with nothing left out."""
    c, arch, params = tiny
    cfg = program.model_config(c)
    cfg = dataclasses.replace(cfg, attention_backend=backend)
    eng = program.make_engine(params, cfg, dict(
        n_slots=2, n_pages=40, max_seq_pages=8, page_size=8,
        prefill_chunk=16, reserve="optimistic"))
    calls = []
    step = eng._step
    eng._step = lambda *a: calls.append(1) or step(*a)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 22)]
    rids = [eng.submit(p, max_new=24) for p in prompts]
    while min(len(eng.requests[r].out) for r in rids) < 20:
        eng.step()
    sample = []
    for p, rid in zip(prompts, rids):
        r = eng.requests[rid]
        assert r.state == "decoding"
        sample.append(check.Served(p, list(r.out), kv=program.cache_of(
            eng, r, -(-r.n_cached // 8))))
    got = check.compare(arch.Reference(c, params), sample, 256)
    assert got["token_gap"] <= 1e-4, got
    assert got["kv_rel_err"] <= 1e-5, got
    # the routed work of each decode step: both slots' rows, 2 experts a
    # row, 4 layers
    m = eng.metrics
    assert m["moe_assignments"] == 2 * 2 * 4 * len(calls)
    assert 0 < m["moe_experts_active"] <= m["moe_assignments"]


def test_cell_runs_and_is_correct(tiny, tmp_path, monkeypatch):
    """Served, traced and checked through ``run_cell``; the control (three
    bf16 passes) fails the same limits.  The trace's directory goes under
    this test's own temporary directory: a test running beside it looks
    for stray trace directories in the shared one."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    c, arch, _ = tiny
    bm = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    mix = tiny_traffic()
    cell = spec.Cell(name="mellum-tiny", chips=1, config=c, arch=arch,
                     traffic=mix, end_to_end=bm["end_to_end"],
                     per_layer=bm["per_layer"])
    res = run.run_cell(cell, SEED, 1.0, True, require_tpu=False,
                       compile_cache=False, control=True)
    assert res["correct"], res["checks"]
    assert not res["control_correct"], res["control"]
    assert "moe_experts_roofline" not in res["metrics"]   # no device ops


# ---------------------------------------------------------------------------
# the expert roofline's reader
# ---------------------------------------------------------------------------

def _trace(n_ops, op_ns=1_000_000):
    """One traced decode step: the harness's span, one ``jit_step``
    execution, ``n_ops`` expert ops inside it, one paged-attention op and
    one expert op of a prefill outside it."""
    ev = [Event("/host:CPU", "python", SPAN_STEP, 0.0, 100e6),
          Event("/device:TPU:0", "XLA Modules", "jit_step(1)", 1e6, 90e6),
          Event("/device:TPU:0", "XLA Modules", "jit_prefill(2)", 92e6, 5e6),
          Event("/device:TPU:0", "XLA Ops", "%paged_attn_pallas.3", 2e6,
                1e6, "%paged_attn_pallas.3")]
    for i in range(n_ops):
        t = 5e6 + i * 2 * op_ns
        ev.append(Event("/device:TPU:0", "XLA Ops", f"%moe_experts.{i}", t,
                        op_ns, f"%moe_experts.{i} fusion"))
    ev.append(Event("/device:TPU:0", "XLA Ops", "%moe_experts.99", 93e6,
                    op_ns, "%moe_experts.99"))
    return summarize(ev, (SPAN_STEP,))


def _run(summary, counters):
    c = published()
    step = StepRecord(0.0, 0.1, decode_ctx=[3000] * 48, chunks=[])
    return Run(config=c, traffic={}, window=Window(0.0, 1.0, [], 0),
               steps=[step, step], traced=(1, 2), summary=summary,
               counters=counters, dims=MELLUM.dims(c),
               peaks=R.peaks("TPU v5 lite"))


def test_expert_roofline_reader_by_hand():
    """Window counters over 2 decode steps: 3,072 assignments and 500
    active experts, so per step 1,536 rows routed and 250 experts read;
    12 traced expert ops of 1 ms inside the one traced step."""
    read = spec.metric_reader("moe_experts_roofline")
    run_ = _run(_trace(12), {"moe_assignments": 3072,
                             "moe_experts_active": 500})
    d, f = 2304, 896
    nbytes = 250 * 3 * d * f * 4 + 1536 * 3 * (d + f) * 4
    ops = 1536 * 2 * 3 * d * f
    need = max(ops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > ops / 197e12             # memory-bound
    assert read(run_) == pytest.approx(100 * need / 12e-3)
    assert 0 < read(run_) < 100


@pytest.mark.parametrize("n_ops,counters", [
    (12, {}),                                        # the parent: no counter
    (12, {"moe_assignments": 0, "moe_experts_active": 0}),   # dense
    (11, {"moe_assignments": 3072, "moe_experts_active": 500}),
    (0, {"moe_assignments": 3072, "moe_experts_active": 500}),
])
def test_expert_roofline_reads_nothing_without_its_inputs(n_ops, counters,
                                                          capsys):
    read = spec.metric_reader("moe_experts_roofline")
    assert read(_run(_trace(n_ops), counters)) is None
    assert "moe_experts_roofline" in capsys.readouterr().err
