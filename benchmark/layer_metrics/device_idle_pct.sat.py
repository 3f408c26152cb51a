"""Share of the traced window (a few seconds at the end of the measured
window) in which no operation ran on the device: 1 - union of device
operation intervals over the traced span (``trace.reduce``)."""

LAYER = "device"
MOVES = "out_tokens_per_s"
UNIT = "%"


def read(run):
    t = run.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
