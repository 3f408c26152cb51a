"""Host wall time of one mixed dispatch (prefill chunks riding the decode
rows), median over the engine's bounded recent window:
``stats()["dispatch_latency"]["mixed"]``. The host clock around a dispatch
that ends in a blocking fetch; not device time."""

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "ms"


def read(run):
    row = run.get("stats_after", {}).get("dispatch_latency", {}).get("mixed")
    if not row or row.get("p50_ms") is None:
        return None
    return row["p50_ms"]
