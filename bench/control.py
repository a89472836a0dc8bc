#!/usr/bin/env python3
"""Readings for the limits of a cell's check, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 10 [--fault token] [--out readings.jsonl]

For each seed: one run of the cell (a short window at the cell's own
load) and, on the same sample, the numbers of the program and of its
control (the reference put in the program's place at the precision below
the configuration's: ``control`` in its file).  ``--fault token`` breaks
the timed path first: every token the decode step produces is altered.
The benchmark's own runs never run any of this.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def alter_tokens(vocab: int):
    """Engine hook: the decode step serves token + 1 in every slot."""
    def hook(engine):
        orig = engine._step

        def step(params, layers, tokens, pages, lens):
            toks, layers = orig(params, layers, tokens, pages, lens)
            return (toks + 1) % vocab, layers
        engine._step = step
    return hook


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", choices=("token",), default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from bench.run import run_cell
    from bench.spec import load_cell
    cell = load_cell(args.workload)
    hook = alter_tokens(int(cell.config["vocab_size"])) \
        if args.fault == "token" else None
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = run_cell(cell, seed, args.seconds, False,
                           engine_hook=hook, control=hook is None)
            line = {"workload": args.workload, "seed": seed,
                    "fault": args.fault, "correct": res["correct"],
                    "program": {k: v["value"]
                                for k, v in res["checks"].items()},
                    "control": res.get("control"),
                    "control_correct": res.get("control_correct"),
                    "metrics": {k: v["value"]
                                for k, v in res["metrics"].items()},
                    "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
