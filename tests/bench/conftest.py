"""Shared set-up for the benchmark's CPU tests: the checkout root on
``sys.path`` (for ``import bench``) and a tiny cell built from the same
kind of files as the real ones."""
from __future__ import annotations

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_config(name="qwen-tiny", layers=2, encoded=False) -> dict:
    """Published file format, toy sizes (CPU only)."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "qwen1.5-0.5b-enc-exact-8L.json"
                           if encoded else "qwen1.5-0.5b.json")) as f:
        c = json.load(f)
    c = copy.deepcopy(c)
    sizes = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                 num_key_value_heads=4, num_hidden_layers=layers,
                 vocab_size=512)
    c.update(sizes, name=name)
    c["program"].update(arch=name, n_layers=layers, d_model=64, n_heads=4,
                        n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=512)
    if encoded:
        c["encoded"]["calib_seq"] = 16
    return c


def tiny_traffic(loop="closed") -> dict:
    t = {"why": "tiny", "loop": loop,
         "prompt_len": {"dist": "uniform", "min": 20, "max": 40},
         "output_len": {"dist": "uniform", "min": 8, "max": 24},
         "engine": {"n_slots": 4, "n_pages": 21, "max_seq_pages": 5,
                    "page_size": 16, "prefill_chunk": 32},
         "sample": {"finished": 3, "in_flight": 3}}
    if loop == "closed":
        t.update(streams=4, rounds=64)
        t["engine"]["reserve"] = "optimistic"
    else:
        t.update(rate_per_s=20.0, block=16,
                 interarrival={"dist": "exponential"})
    return t


@pytest.fixture
def tiny_cell():
    """A Cell over the tiny configuration, reporting every metric the
    real cells report."""
    from bench.spec import Cell, arch_module, load_json
    bm = load_json(os.path.join(ROOT, "BENCHMARK.json"))

    def make(encoded=False, loop="closed", limits=None, extra_layer=()):
        c = tiny_config(encoded=encoded)
        if limits is not None:
            c["check"] = dict(limits)
        return Cell(name="tiny", chips=1, config=c, arch=arch_module(c),
                    traffic=tiny_traffic(loop), end_to_end=bm["end_to_end"],
                    per_layer=bm["per_layer"] + list(extra_layer))
    return make
