"""Requests the engine preempted inside the window (``stats()`` count at
the window's end minus its start)."""

LAYER = "engine step"
MOVES = "ttft_p90_ms"
UNIT = "count"


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b:
        return None
    return b["preemptions"] - a["preemptions"]
