"""How late the load generator sent: 90th percentile of (sent - due) over
the requests due in the window, on the generator's own clock. A starved
generator must not be read as a fast server."""

LAYER = "entry"
MOVES = "ttft_p90_ms"
UNIT = "ms"


def read(run):
    late = [r["late"] for r in run.get("records", ())]
    if not late:
        return None
    from benchmark.metrics import percentile
    return percentile(late, 90) * 1e3
