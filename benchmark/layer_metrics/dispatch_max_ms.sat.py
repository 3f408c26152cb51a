"""The window's longest dispatch, in milliseconds of host wall time from
the ``serve:dispatch`` span's start to the ``serve:copy`` span's end,
every kind together: the histogram ``dispatch_s`` of ``stats()["spans"]``
at the window's end minus its start (cumulative bucket counts subtract),
read as the upper edge of the highest bucket that holds a sample of the
window. A bucket is 33% wide; a stall of seconds against dispatches of
tens or hundreds of milliseconds reads plainly. A program without the
histogram reads as nothing."""

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "ms"

HISTOGRAM = "dispatch_s"


def read(run):
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or "spans" not in a or "spans" not in b:
        return None
    hb = b["spans"]["histograms"].get(HISTOGRAM)
    if not hb:
        return None
    ha = a["spans"]["histograms"].get(HISTOGRAM)
    cum = list(hb["cumulative"])
    if ha:
        cum = [x - y for x, y in zip(cum, ha["cumulative"])]
    n = cum[-1]
    if n <= 0:
        return None
    # the first bucket whose cumulative count holds every sample is the
    # highest that holds one; the bucket above the last edge reads as it
    top = next(i for i, c in enumerate(cum) if c >= n)
    return 1e3 * hb["le"][min(top, len(hb["le"]) - 1)]
