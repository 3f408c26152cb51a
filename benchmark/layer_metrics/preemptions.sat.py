"""Requests the engine preempted inside the window (``stats()`` count at
the window's end minus its start): in a saturated cell each one is a
prompt computed again, which is tokens per second lost."""

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "count"


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or "preemptions" not in b:
        return None
    return b["preemptions"] - a.get("preemptions", 0)
