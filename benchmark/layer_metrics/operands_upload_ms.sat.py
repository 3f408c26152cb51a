"""Host milliseconds per dispatch spent handing a dispatch's operands to
the device: the seconds of the engine's ``serve:upload`` span (one
``jnp.asarray`` an operand, the LoRA operand included) over the
dispatches of the window (``chunks``). It is the transfer part of what
``step_host_ms.sat`` counts as ``serve:operands``; the rest is
``serve:build``, the numpy operands made on the host. No dispatch is in
flight while it runs. A program without the span reads as nothing."""

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "ms"

SPAN = "serve:upload"


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
