"""Host milliseconds per dispatch that the pump thread spends in the front
line around the engine step: taking commands off the queue, routing
finishes, handing a step's tokens to the event loop, and the supervisor's
bookkeeping (the ``serve:cmds``, ``serve:route``, ``serve:deliver`` and
``serve:supervise`` spans of ``stats()["spans"]``), over the window. No
dispatch is in flight while it runs, so the chip waits for all of it."""

LAYER = "front line"
MOVES = "out_tokens_per_s"
UNIT = "ms"

SPANS = ("serve:cmds", "serve:route", "serve:deliver", "serve:supervise")


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or "spans" not in a or "spans" not in b:
        return None
    dispatches = b["chunks"] - a["chunks"]
    if dispatches <= 0:
        return None
    sa, sb = a["spans"]["spans"], b["spans"]["spans"]
    seconds = sum(sb.get(n, {}).get("seconds", 0.0) -
                  sa.get(n, {}).get("seconds", 0.0) for n in SPANS)
    return 1e3 * seconds / dispatches
