"""The window-bounded paged kernel of the sliding layers against the chip's
roofline: the time its operations and bytes over the measured window would
take at the published peaks (the larger of the two), over the device
seconds it took.

Operations and bytes: ``window_attention_counts`` of the family's model
file on the window's ``window_tokens_read`` (live cache tokens a query
lane attended, at most the window's width a lane, summed over lanes and
layers: the operations) and ``window_tokens_copied`` (whole pages a row's
call copied, which its lanes share: the bytes). Device seconds: the self
time of the operations named ``paged_attention_window*`` inside the TRACED
slice, scaled by ``window_s`` over the slice's length, which assumes the
traced seconds are like the rest of the window."""

import os

LAYER = "kernels"
MOVES = "out_tokens_per_s"
UNIT = "%"
KIND, PREFIXES = "window", ("paged_attention_window",)


def read_kind(run, kind, prefixes):
    """The share for one kind of layer (``full`` or ``window``): the
    family's ``<kind>_attention_counts`` on the counters ``<kind>_tokens_
    read`` / ``_copied``, over the operations named ``prefixes*``."""
    from benchmark import harness
    shared = harness.load_by_name(
        "layer_metrics", "moe_grouped_roofline_pct.sat",
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))])
    got = shared.family_counts(run)
    if got is None:
        return None
    model, widths, delta = got
    counts = getattr(model, kind + "_attention_counts", None)
    read = delta.get(kind + "_tokens_read", 0)
    if counts is None or read <= 0:
        return None
    # ``startswith`` takes a tuple: the prefixes' seconds add up
    return shared.roofline_pct(
        run, counts(widths, read, delta.get(kind + "_tokens_copied")),
        tuple(prefixes))


def read(run):
    return read_kind(run, KIND, PREFIXES)
