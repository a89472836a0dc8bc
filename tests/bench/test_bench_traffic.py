"""The traffic generator: deterministic per seed, the stated length
statistics, the same work on every seed, and mixes found by name."""
import json
import os
import shutil
from collections import Counter

import numpy as np
import pytest

from bench import spec, traffic as T
from conftest import ROOT

BIG_SEED = 2 ** 33 + 12345


CHAT = {"loop": "open", "rate_per_s": 4.0, "block": 64,
        "interarrival": {"dist": "exponential"},
        "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                       "min": 32, "max": 2048},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                       "min": 16, "max": 512}}


def _mix(name):
    if name == "chat":
        return dict(CHAT)
    return spec.load_json(spec.traffic_path(name))


@pytest.mark.parametrize("name", ["decode-batch", "chat"])
def test_deterministic_per_seed(name):
    mix = _mix(name)
    a = T.make_requests(mix, BIG_SEED, 30, 151936)
    b = T.make_requests(mix, BIG_SEED, 30, 151936)
    c = T.make_requests(mix, BIG_SEED + 1, 30, 151936)
    assert [(r.due_s, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.due_s, r.max_new, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    assert all(0 <= int(r.prompt.min()) and int(r.prompt.max()) < 151936
               for r in a)


def test_every_seed_does_the_same_work():
    """A whole block holds the same lengths on every seed, in another
    order."""
    mix = _mix("decode-batch")
    s = mix["streams"]
    a = T.make_requests(mix, 1, 30, 1000)[:s]
    b = T.make_requests(mix, 2, 30, 1000)[:s]
    assert Counter(r.max_new for r in a) == Counter(r.max_new for r in b)
    assert Counter(len(r.prompt) for r in a) == \
        Counter(len(r.prompt) for r in b)
    assert [r.max_new for r in a] != [r.max_new for r in b]


def test_uniform_length_statistics():
    mix = _mix("decode-batch")
    reqs = T.make_requests(mix, 7, 30, 1000)
    out = np.asarray([r.max_new for r in reqs])
    plen = np.asarray([len(r.prompt) for r in reqs])
    assert out.min() >= 256 and out.max() <= 1024
    assert plen.min() >= 32 and plen.max() <= 128
    assert abs(out.mean() - 640) < 10 and abs(plen.mean() - 80) < 2
    assert [r.stream for r in reqs[:3]] == [0, 1, 2]


def test_lognormal_and_poisson_statistics():
    mix = _mix("chat")
    seconds = 40
    reqs = T.make_requests(mix, 3, seconds, 1000)
    plen = np.asarray([len(r.prompt) for r in reqs])
    out = np.asarray([r.max_new for r in reqs])
    assert plen.min() >= 32 and plen.max() <= 2048
    assert out.min() >= 16 and out.max() <= 512
    assert abs(np.median(plen) - 512) <= 20
    assert abs(np.median(out) - 128) <= 6
    due = np.asarray([r.due_s for r in reqs])
    assert np.all(np.diff(due) >= 0) and due[0] == 0.0
    # arrivals outlast the window, at the stated mean rate
    assert due[-1] > seconds
    gaps = np.diff(due)
    assert abs(1.0 / gaps.mean() - mix["rate_per_s"]) < 0.1 * \
        mix["rate_per_s"]
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.15)


def test_extra_traffic_file_found_by_name(tmp_path):
    """A later mix is a data file and an entry: nothing else changes."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    bm = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mix = _mix("chat")
    mix.update(engine=_mix("decode-batch")["engine"], rate_per_s=1.5)
    (root / "bench" / "traffic" / "chat-slow.json").write_text(
        json.dumps(mix))
    bm["workloads"].append({"name": "dense-chat-slow",
                            "config": "qwen1.5-0.5b",
                            "traffic": "chat-slow", "chips": 1,
                            "why": "slow chat"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = spec.load_cell("dense-chat-slow", root=str(root))
    assert cell.traffic["rate_per_s"] == 1.5
    assert cell.config["name"] == "qwen1.5-0.5b"
    # reported: the end-to-end metrics without a cell list, and the
    # per-layer metrics without one that move them
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms",
                                                    "setup_s"]
    reqs = T.make_requests(cell.traffic, 5, 10, 100)
    assert len(reqs) >= 1.5 * 10
