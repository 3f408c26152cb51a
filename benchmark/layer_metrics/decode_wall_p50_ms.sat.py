"""Host wall time of one decode dispatch of up to ``decode_chunk``
iterations, median over the engine's bounded recent window:
``stats()["dispatch_latency"]["decode"]``. Not device time."""

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "ms"


def read(run):
    row = run.get("stats_after", {}).get("dispatch_latency", {}).get("decode")
    if not row or row.get("p50_ms") is None:
        return None
    return row["p50_ms"]
