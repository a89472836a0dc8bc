"""Layer: serve/engine.py.  Mean share of slots that did work per engine
step over the window: the engine's ``occupancy_sum`` counter delta over
its ``engine_steps`` delta.  Moves output_tokens_per_s."""


def read(run):
    steps = run.counters.get("steps", 0)
    if not steps:
        return None
    return 100.0 * run.counters["occupancy_sum"] / steps
