"""Backend compilations jax reported between the window's edges
(``metrics.CompileClock``). Must read 0: every shape is warmed in set-up."""

LAYER = "paged programs"
MOVES = "tpot_p90_ms"
UNIT = "count"


def read(run):
    return run.get("compiles_in_window")
