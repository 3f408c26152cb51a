"""How unevenly the held experts are loaded, over the window: the largest
row count of one held expert over the mean row count of the held experts,
averaged over the layer calls (``moe_rows_max`` x experts held /
``moe_pairs_local``; 1 = even). The grouped matmul walks its row tiles
expert by expert, so the fullest expert sets how long a call's tail is;
with a few rows an expert, the serving regime, every call costs its
weight stream and this ratio says how far the routing is from uniform."""

LAYER = "paged programs"
MOVES = "out_tokens_per_s"
UNIT = "ratio"


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or "spans" not in a or "spans" not in b or \
            not b.get("model"):
        return None
    ca, cb = a["spans"]["counters"], b["spans"]["counters"]
    local = cb.get("moe_pairs_local", 0) - ca.get("moe_pairs_local", 0)
    if local <= 0:
        return None
    rows_max = cb.get("moe_rows_max", 0) - ca.get("moe_rows_max", 0)
    return rows_max * b["model"]["n_local_experts"] / local
