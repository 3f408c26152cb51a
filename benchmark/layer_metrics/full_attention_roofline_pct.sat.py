"""The paged kernel of the full-attention layers against the chip's
roofline, for a family that counts what those layers read apart from its
window layers: ``full_attention_counts`` of the family's model file on the
window's ``full_tokens_read`` (operations) and ``full_tokens_copied``
(bytes), over the scaled self time of the operations named
``paged_attention_q1*`` and ``paged_attention_mq*`` (the window-bounded
form carries other names). The arithmetic is
``window_attention_roofline_pct.sat``'s."""

import os

LAYER = "kernels"
MOVES = "out_tokens_per_s"
UNIT = "%"


def read(run):
    from benchmark import harness
    twin = harness.load_by_name(
        "layer_metrics", "window_attention_roofline_pct.sat",
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))])
    return twin.read_kind(run, "full", ("paged_attention_q1",
                                        "paged_attention_mq"))
