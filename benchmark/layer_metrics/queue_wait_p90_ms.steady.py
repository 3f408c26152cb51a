"""90th percentile over the window of a request's wait from the front
line's first sight of it (stamped on the event loop, before the command
queue) to its first admission to a slot: the ``queue_wait_s`` histogram
of ``stats()["spans"]`` at the window's end minus its start (cumulative
bucket counts subtract), read linearly inside the bucket."""

LAYER = "front line"
MOVES = "ttft_p90_ms"
UNIT = "ms"


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or "spans" not in a or "spans" not in b:
        return None
    hb = b["spans"]["histograms"].get("queue_wait_s")
    if not hb:
        return None
    ha = a["spans"]["histograms"].get("queue_wait_s")
    cum = list(hb["cumulative"])
    if ha:
        cum = [x - y for x, y in zip(cum, ha["cumulative"])]
    n = cum[-1]
    if n <= 0:
        return None
    le, rank = hb["le"], 0.9 * n
    for i, c in enumerate(cum):
        if c >= rank and c > 0:
            if i >= len(le):
                return 1e3 * le[-1]
            lo = le[i - 1] if i else 0.0
            below = cum[i - 1] if i else 0
            return 1e3 * (lo + (le[i] - lo) * (rank - below) / (c - below))
    return 1e3 * le[-1]
