"""Mean share of the KV pool's usable blocks in use over the window: the
engine adds the blocks in use to ``kv_blocks_in_use_sum`` once a dispatch,
so its delta over the window's dispatches (``chunks``) is the mean a
dispatch found, against ``usable_blocks``. A pool that reads near 100
preempts (``preemptions.sat``); for a family with window groups the
counter ``kv_window_blocks_in_use_sum`` beside it says how much of that
the bounded groups hold."""

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "%"


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or "spans" not in a or "spans" not in b:
        return None
    ca, cb = a["spans"]["counters"], b["spans"]["counters"]
    dispatches = b.get("chunks", 0) - a.get("chunks", 0)
    if "kv_blocks_in_use_sum" not in cb or dispatches <= 0 or \
            not b.get("usable_blocks"):
        return None
    held = cb["kv_blocks_in_use_sum"] - ca.get("kv_blocks_in_use_sum", 0)
    return 100.0 * held / dispatches / b["usable_blocks"]
