"""Backend compilations jax reported between the window's edges
(``metrics.CompileClock``). Must read 0: every shape is warmed in set-up."""

LAYER = "train step"
MOVES = "train_tokens_per_s"
UNIT = "count"


def read(run):
    return run.get("compiles_in_window")
