"""Host wall milliseconds per decode ITERATION over the window: seconds
of the decode dispatches' ``serve:dispatch`` + ``serve:fetch`` spans over
the iterations those dispatches ran (``decode_iterations``). A dispatch
runs 8 iterations or fewer, so wall per dispatch is two-valued
(``decode_wall_p50_ms.sat``); wall per iteration is not."""

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "ms"


def _decode_seconds(stats):
    spans = stats["spans"]["spans"]
    return sum(spans.get(n, {}).get("kinds", {}).get("decode", {})
               .get("seconds", 0.0)
               for n in ("serve:dispatch", "serve:fetch"))


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or "spans" not in a or "spans" not in b:
        return None
    iters = (b["spans"]["counters"].get("decode_iterations", 0) -
             a["spans"]["counters"].get("decode_iterations", 0))
    if iters <= 0:
        return None
    return 1e3 * (_decode_seconds(b) - _decode_seconds(a)) / iters
