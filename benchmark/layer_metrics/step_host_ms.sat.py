"""Host milliseconds per dispatch that the engine step spends outside the
device call: planning (expiry, retirement, admission, block planning),
building and uploading operands, committing tokens and the step-boundary
journal work (the ``serve:plan``, ``serve:operands``, ``serve:commit`` and
``serve:journal`` spans of ``stats()["spans"]``), over the window. No
dispatch is in flight while it runs, so the chip waits for all of it."""

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "ms"

SPANS = ("serve:plan", "serve:operands", "serve:commit", "serve:journal")


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
