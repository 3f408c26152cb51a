"""Occupied decode slots, sampled once a second inside the window, as a
share of ``max_slots``."""

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "%"


def read(run):
    samples = run.get("samples")
    if not samples:
        return None
    mean = sum(s["live_slots"] for s in samples) / len(samples)
    return 100.0 * mean / run["max_slots"]
