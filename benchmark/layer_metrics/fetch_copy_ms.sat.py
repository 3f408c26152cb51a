"""Host milliseconds per dispatch spent bringing a finished program's
outputs to the host: the seconds of the engine's ``serve:copy`` span (the
outputs' copies, the family's counter array, and in a batched prefill the
first-token pick) over the dispatches of the window (``chunks``). The
program has ended and nothing is queued behind it, so the device waits
for all of it. The wait for the program itself is ``serve:wait``, not
read here. A program without the span reads as nothing."""

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "ms"

SPAN = "serve:copy"


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or "spans" not in a or "spans" not in b:
        return None
    sb = b["spans"]["spans"].get(SPAN)
    dispatches = b["chunks"] - a["chunks"]
    if sb is None or dispatches <= 0:
        return None
    sa = a["spans"]["spans"].get(SPAN, {})
    return 1e3 * (sb["seconds"] - sa.get("seconds", 0.0)) / dispatches
