"""Share of the traced window in which a collective (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute) ran on a device
while no other operation did, averaged over the chips (``trace.reduce``)."""

LAYER = "sharded train step"
MOVES = "train_tokens_per_s"
UNIT = "%"


def read(run):
    t = run.get("trace")
    if not t:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
