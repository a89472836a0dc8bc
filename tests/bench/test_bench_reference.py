"""The plain reference (``bench/arch/Qwen2ForCausalLM.py``) against the
program's own forward at a tiny size, the weights' layout against the
program's parameter tree, and the calibration stream against the
program's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as Rf, spec, weights
from conftest import tiny_config

SEED = 2 ** 35 + 3
QWEN2 = spec.arch_module(tiny_config())


@pytest.fixture(scope="module")
def setup():
    from bench.program import model_config
    c = tiny_config()
    return c, model_config(c), weights.make_params(QWEN2, c, SEED)


def test_weights_layout_is_the_programs(setup):
    from repro.models import init_model
    c, cfg, params = setup
    want = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    assert jax.tree_util.tree_structure(want) == \
        jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_weights_deterministic_per_seed(setup):
    c, _, params = setup
    again = weights.make_params(QWEN2, c, SEED)
    other = weights.make_params(QWEN2, c, SEED + 1)
    leaf = lambda p: np.asarray(p["stack"]["attn"]["wq"])  # noqa: E731
    assert np.array_equal(leaf(params), leaf(again))
    assert not np.array_equal(leaf(params), leaf(other))


def test_reference_matches_the_program_forward(setup):
    from repro.models import apply_model
    c, cfg, params = setup
    toks = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    logits = np.asarray(apply_model(params, cfg, jnp.asarray(toks[None]))[0]
                        )[0]
    ref = QWEN2.Reference(c, params)
    mx, tg, am, ks, vs = ref.run(toks, 256, np.argmax(logits, -1))
    assert np.allclose(mx, logits.max(-1), atol=1e-5)
    assert np.array_equal(am, logits.argmax(-1))
    assert np.allclose(tg, mx, atol=1e-6)          # served = argmax
    assert ks.shape == (2, 256, 4, 16) and vs.shape == ks.shape


def test_bf16_3x_is_between_bf16_and_f32():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 32))
    exact = Rf.matmul(a, b, "highest")
    e3 = float(jnp.abs(Rf.matmul(a, b, "bf16_3x") - exact).max())
    e1 = float(jnp.abs(Rf.matmul(a, b, "bf16") - exact).max())
    assert 0 < e3 < e1 / 50


def test_integer_linear_is_exact():
    x = jnp.asarray([[0.5, -1.0, 0.25]])
    w = jnp.asarray([[1.0], [2.0], [-4.0]])
    out = QWEN2.qlinear(x, w, jnp.float32(1.0), 8, "highest")
    # codes: x -> [64, -127, 32] (scale 1/127), w -> [32, 64, -127]
    sa, sw = 1.0 / 127, 4.0 / 127
    want = (64 * 32 - 127 * 64 - 32 * 127) * sa * sw
    assert float(out[0, 0]) == pytest.approx(want, rel=1e-6)


def test_calibration_stream_is_the_programs():
    from repro.data.synthetic import SyntheticLMDataset
    ds = SyntheticLMDataset(512, 16, seed=3)
    got = list(Rf.calibration_tokens(512, 16, 3, 2, 4))
    for step in range(2):
        assert np.array_equal(got[step], ds.batch(step, 4)["tokens"])


def test_calibration_matches_the_programs_scales():
    """The reference's activation scales come out as the program's own
    calibration records them (to float32 rounding)."""
    from bench.program import serving_params
    import bench.program as P
    c = tiny_config(encoded=True)
    params = weights.make_params(QWEN2, c, SEED)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        old, P.BUNDLE_DIR = P.BUNDLE_DIR, d
        try:
            served, _, _ = serving_params(c, params)
        finally:
            P.BUNDLE_DIR = old
    e = c["encoded"]
    amax = QWEN2.calibrate(c, params, seq_len=e["calib_seq"],
                        seed=e["calib_seed"], n_batches=e["calib_batches"],
                        batch_size=e["calib_batch_size"])
    for block, name in QWEN2.LINEARS:
        prog = np.asarray(served["stack"][block][name + "_as"]) * 127
        assert np.allclose(amax[(block, name)], prog, rtol=1e-5)
