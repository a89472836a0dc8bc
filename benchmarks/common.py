"""Benchmark helpers: JSON artifact cache."""
import json
import os

ART = os.path.join(os.path.dirname(__file__), "artifacts")


def cached(name: str, fn, force: bool = False):
    os.makedirs(ART, exist_ok=True)
    path = os.path.join(ART, name + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    out = fn()
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=float)
    return out
