"""Median time between the finishes of consecutive train steps inside the
window (host clock at ``block_until_ready`` on the loss, one step
dispatched ahead)."""

LAYER = "train step"
MOVES = "train_tokens_per_s"
UNIT = "ms"


def read(run):
    steps = run.get("step_s")
    if not steps:
        return None
    from benchmark.metrics import percentile
    return percentile(steps, 50) * 1e3
