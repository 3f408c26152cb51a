"""Mixed dispatches per request retired inside the window: how many
mixed steps (each paying for every slot's lanes) a request costs."""

LAYER = "paged programs"
MOVES = "out_tokens_per_s"
UNIT = "count"


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or b["retired"] <= a["retired"]:
        return None
    return ((b["mixed_dispatches"] - a["mixed_dispatches"]) /
            (b["retired"] - a["retired"]))
