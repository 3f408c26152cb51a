"""Share of the mixed steps' query lanes that carried a real token over
the window: a mixed dispatch computes ``max_slots x Q`` lanes (``Q`` the
bucket of the longest chunk) while a decode row uses one lane and a pad
row none (``mixed_lanes_real`` / ``mixed_lanes_total``). What a packed
mixed step could save."""

LAYER = "paged programs"
MOVES = "out_tokens_per_s"
UNIT = "%"


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or "spans" not in a or "spans" not in b:
        return None
    ca, cb = a["spans"]["counters"], b["spans"]["counters"]
    total = cb.get("mixed_lanes_total", 0) - ca.get("mixed_lanes_total", 0)
    if total <= 0:
        return None
    real = cb.get("mixed_lanes_real", 0) - ca.get("mixed_lanes_real", 0)
    return 100.0 * real / total
