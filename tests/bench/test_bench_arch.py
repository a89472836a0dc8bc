"""Architecture modules (``bench/arch``): the Qwen2 module reads what the
harness read before it became a module (constants recorded from the
harness as it was, with every Qwen2 piece in ``weights``,
``reference`` and ``roofline``); a module the harness has never seen
plugs in through its file alone; the default cache reader joins the
program's stages; a missing module names the file to add."""
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, roofline as R, run, spec, weights
from conftest import ROOT, tiny_config, tiny_traffic
from test_bench_run import LIMITS, SECONDS, _alter_tokens

SEED = 2 ** 35 + 3
QWEN2 = spec.arch_module(tiny_config())
PROMPT = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)

# float64 sum and sum of squares of each leaf of the tiny configuration's
# weights for SEED, as the harness made them before the move
LEAVES = {
    "embed/table": (6.634332308550086, 12.95938315038046),
    "final_norm_g": (65.62477964162827, 67.86280734782665),
    "stack/attn/wk": (-1.612665624194051, 129.144884422171),
    "stack/attn/wk_b": (0.44316183772752993, 0.04638390826929935),
    "stack/attn/wo": (3.0646671707745554, 127.63175612287017),
    "stack/attn/wq": (26.206339992175344, 128.2487458336305),
    "stack/attn/wq_b": (0.2788540024012036, 0.05263752441831575),
    "stack/attn/wv": (-0.06645293261817642, 129.75617835296836),
    "stack/attn/wv_b": (0.13867582892999053, 0.04457601189245543),
    "stack/ln1_g": (128.94754654169083, 131.11501863963215),
    "stack/ln2_g": (128.49133163690567, 130.38429795615372),
    "stack/mlp/wg": (-36.067578400885395, 258.38602054443345),
    "stack/mlp/wi": (-20.30875843693866, 255.61696098207412),
    "stack/mlp/wo": (-0.9096110016059811, 128.01230828028412),
}
# the reference's largest logit at each position of PROMPT, before the move
MAX_LOGITS = [
    0.62441086769104, 0.4692360758781433, 0.6874721646308899,
    0.5091365575790405, 0.5053069591522217, 0.509814977645874,
    0.45628252625465393, 0.4774891436100006, 0.4901154637336731,
    0.4524611532688141, 0.49209845066070557, 0.4720124304294586,
    0.4141817092895508, 0.46621599793434143, 0.5017628073692322,
    0.5377100706100464, 0.44880014657974243, 0.4716031849384308,
    0.4552571773529053, 0.4776236414909363, 0.49868065118789673,
    0.49709662795066833, 0.5717727541923523, 0.6894951462745667,
    0.466046541929245, 0.46331483125686646, 0.5130405426025391,
    0.625417947769165, 0.524506151676178, 0.574451208114624,
    0.4084855616092682, 0.4684060215950012, 0.5300648808479309,
    0.5117747187614441, 0.5593874454498291, 0.5620164275169373,
    0.5999373197555542, 0.48164936900138855, 0.4676303565502167,
    0.4426117539405823]
# float64 sum and sum of squares of the reference's K and V over PROMPT
KV = {"k": (-194.29292876007094, 5410.758122124995),
      "v": (-196.35833220137283, 5263.9464085140335)}
# the encoded configuration's calibrated activation scales per linear and
# layer, and the sum of the largest logits over PROMPT of the integer-code
# reference (8 and 4 bits) and of three bf16 passes, before the move
AMAX = {
    ("attn", "wq"): [4.130409240722656, 3.612220525741577],
    ("attn", "wk"): [4.130409240722656, 3.612220525741577],
    ("attn", "wv"): [4.130409240722656, 3.612220525741577],
    ("attn", "wo"): [3.4147825241088867, 3.3739678859710693],
    ("mlp", "wi"): [4.312682151794434, 3.868802785873413],
    ("mlp", "wg"): [4.312682151794434, 3.868802785873413],
    ("mlp", "wo"): [7.922502040863037, 7.339222431182861],
}
MODE_LOGIT_SUMS = {("highest", 8): 20.247383028268814,
                   ("highest", 4): 20.70740434527397,
                   ("bf16_3x", 0): 20.37478259205818}
# (matmul_params, token_flops(., 100), prefill_chunk_flops(., 64, 32),
# paged_attn_call(., [10, 20])) of the real configurations, before the move
DIMS = {
    "qwen1.5-0.5b": (463_863_808, 937558016.0, 29940514816.0,
                     (122880.0, 262144.0)),
    "qwen1.5-0.5b-enc-exact-8L": (258_342_912, 519962624.0, 16618356736.0,
                                  (122880.0, 262144.0)),
}


def _sums(x):
    a = np.asarray(x, np.float64)
    return float(a.sum()), float((a * a).sum())


@pytest.fixture(scope="module")
def params():
    return weights.make_params(QWEN2, tiny_config(), SEED)


def test_weights_are_those_before_the_move(params):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    got = {"/".join(k.key for k in path): _sums(leaf)
           for path, leaf in flat}
    assert list(got) == list(LEAVES)          # leaf order: fold_in index
    for name, want in LEAVES.items():
        assert got[name] == pytest.approx(want, rel=1e-9), name


def test_reference_reads_what_it_read_before_the_move(params):
    mx, _, _, ks, vs = QWEN2.Reference(tiny_config(), params).run(
        PROMPT, 256, np.zeros(40, np.int32))
    assert mx.tolist() == pytest.approx(MAX_LOGITS, rel=1e-6, abs=1e-7)
    assert _sums(ks[:, :40]) == pytest.approx(KV["k"], rel=1e-6)
    assert _sums(vs[:, :40]) == pytest.approx(KV["v"], rel=1e-6)


def test_quantized_reference_and_control_read_as_before_the_move():
    c = tiny_config(encoded=True)
    p = weights.make_params(QWEN2, c, SEED)
    e = c["encoded"]
    amax = QWEN2.calibrate(c, p, seq_len=e["calib_seq"],
                           seed=e["calib_seed"], n_batches=e["calib_batches"],
                           batch_size=e["calib_batch_size"])
    for k, want in AMAX.items():
        assert amax[k].tolist() == pytest.approx(want, rel=1e-6), k
    for (mode, bits), want in MODE_LOGIT_SUMS.items():
        ref = QWEN2.Reference(c, p, mode=mode, bits=bits,
                              amax=amax if bits else None)
        mx = ref.run(PROMPT, 256, np.zeros(40, np.int32))[0]
        assert _sums(mx)[0] == pytest.approx(want, rel=1e-6), (mode, bits)


@pytest.mark.parametrize("name", sorted(DIMS))
def test_dims_are_those_before_the_move(name):
    c = spec.load_json(os.path.join(spec.BENCH, "configs", f"{name}.json"))
    d = spec.arch_module(c).dims(c)
    mp, tok, chunk, attn = DIMS[name]
    assert d.matmul_params == mp
    assert R.token_flops(d, 100) == tok
    assert R.prefill_chunk_flops(d, 64, 32) == chunk
    assert R.paged_attn_call(d, [10, 20]) == attn
    # the step's least time is the parent's: every layer at one call's
    assert R.paged_attn_step_s(d, [10, 20], 197e12, 819e9) == \
        d.layers * R.roofline_s(*attn, 197e12, 819e9)
    assert d.window_layers() == [(None, d.layers)]


# ---------------------------------------------------------------------------
# a new architecture, brought as a module
# ---------------------------------------------------------------------------

def _toy_checkout(tmp_path):
    """A checkout root holding only the toy's files: its module, its
    configuration and a traffic mix.  The harness itself is the real
    one."""
    root = tmp_path / "checkout"
    for d in ("arch", "configs", "traffic"):
        (root / "bench" / d).mkdir(parents=True)
    shutil.copy(os.path.join(os.path.dirname(__file__), "data",
                             "ToyForCausalLM.py"),
                root / "bench" / "arch" / "ToyForCausalLM.py")
    c = tiny_config(name="toy")
    for k in ("reduced", "assumed", "encoded", "weight_seeds"):
        c.pop(k, None)
    c.update(architectures=["ToyForCausalLM"], num_key_value_heads=2,
             head_dim=24, tie_word_embeddings=False, check=dict(LIMITS))
    c["program"].update(arch="toy", n_kv_heads=2, head_dim=24,
                        qkv_bias=False, tie_embeddings=False)
    with open(root / "bench" / "configs" / "toy.json", "w") as f:
        json.dump(c, f)
    with open(root / "bench" / "traffic" / "tiny.json", "w") as f:
        json.dump(tiny_traffic(), f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"] = [{"name": "toy", "source": "a test", "reduced": [],
                      "file": "bench/configs/toy.json", "why": "a test"}]
    bm["workloads"] = [{"name": "toy-tiny", "config": "toy",
                        "traffic": "tiny", "chips": 1, "why": "a test"}]
    return str(root), bm


def test_new_architecture_plugs_in_by_its_module(tmp_path):
    """Built, served, traced and checked through ``run_cell``, with no
    file of the harness touched; head width and output head are the
    toy's own, and the program serves them."""
    root, bm = _toy_checkout(tmp_path)
    cell = spec.load_cell("toy-tiny", root=root, benchmark=bm)
    assert cell.arch.__file__.startswith(root)
    p = weights.make_params(cell.arch, cell.config, SEED)
    assert p["lm_head"]["w"].shape == (64, 512)
    assert p["stack"]["attn"]["wq"].shape == (2, 64, 4 * 24)
    res = run.run_cell(cell, SEED, SECONDS, True, require_tpu=False,
                       compile_cache=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["kv_rel_err"]["value"] is not None
    assert res["attempted"] > 0 and res["failed"] == 0


def test_new_architecture_broken_timed_path_is_not_correct(tmp_path):
    root, bm = _toy_checkout(tmp_path)
    cell = spec.load_cell("toy-tiny", root=root, benchmark=bm)
    res = run.run_cell(cell, SEED, SECONDS, False, require_tpu=False,
                       compile_cache=False, engine_hook=_alter_tokens)
    assert not res["correct"], res["checks"]


def test_missing_module_names_the_file_to_add():
    c = dict(tiny_config(), architectures=["MellumForCausalLM"])
    with pytest.raises(FileNotFoundError,
                       match="add bench/arch/MellumForCausalLM.py"):
        spec.arch_module(c)
    with pytest.raises(ValueError, match="no module name"):
        spec.arch_module(dict(c, architectures=["../../evil"]))


def test_encoded_configuration_needs_calibrate(tmp_path):
    root, _ = _toy_checkout(tmp_path)
    c = dict(tiny_config(encoded=True), architectures=["ToyForCausalLM"])
    with pytest.raises(ValueError, match="has no calibrate"):
        spec.arch_module(c, root)


# ---------------------------------------------------------------------------
# the default cache reader
# ---------------------------------------------------------------------------

class _FakeKV:
    """``engine.kv`` of a program with a dense prefix and an expert
    stack: per stage the per-head pools (layers, pages, page size, kv
    heads, head dim), and one slot's page table."""

    def __init__(self):
        def pool(layers, base):
            n = layers * 5 * 4 * 2 * 3
            return (base + jnp.arange(n, dtype=jnp.float32)).reshape(
                layers, 5, 4, 2, 3)
        self.layers = {
            "dense_prefix": {"pool_k": pool(1, 0.0),
                             "pool_v": pool(1, 1000.0)},
            "moe_stack": {"pool_k": pool(3, 2000.0),
                          "pool_v": pool(3, 3000.0)},
        }
        self.ptab = np.asarray([[0, 0, 0], [4, 2, 0]], np.int32)


class _Req:
    slot = 1


def test_default_cache_of_joins_the_stages_in_order():
    eng = type("Engine", (), {"kv": _FakeKV()})()
    k, v = program.cache_of(eng, _Req(), 2)
    assert k.shape == v.shape == (4, 2 * 4, 2, 3)
    for got, name in ((k, "pool_k"), (v, "pool_v")):
        want = np.concatenate([
            np.asarray(eng.kv.layers[s][name])[:, [4, 2]].reshape(
                -1, 8, 2, 3) for s in ("dense_prefix", "moe_stack")])
        assert np.array_equal(got, want)
    assert k[0, 0, 0, 0] == 4 * 24 and k[1, 0, 0, 0] == 2000 + 4 * 24
