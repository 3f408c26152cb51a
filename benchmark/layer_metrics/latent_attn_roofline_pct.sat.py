"""The latent paged-attention kernel against the chip's roofline: the time
its operations and bytes over the measured window would take at the
published peaks (the larger of the two), over the device seconds it took.

Operations and bytes: ``latent_attention_counts`` of the family's model
file on the window's ``latent_tokens_read`` (live cache tokens a query
lane, summed over lanes and layers; a token is the compressed vector and
the rotary key, 1152 B at the published widths in bf16). Device seconds:
the self time of the operations named ``paged_attention_latent`` inside
the TRACED slice, scaled by ``window_s`` over the slice's length, which
assumes the traced seconds are like the rest of the window."""

import os

LAYER = "kernels"
MOVES = "out_tokens_per_s"
UNIT = "%"


def read(run):
    from benchmark import harness
    shared = harness.load_by_name(
        "layer_metrics", "moe_grouped_roofline_pct.sat",
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))])
    got = shared.family_counts(run)
    if got is None:
        return None
    model, widths, delta = got
    if not hasattr(model, "latent_attention_counts") or \
            delta.get("latent_tokens_read", 0) <= 0:
        return None
    return shared.roofline_pct(run, model.latent_attention_counts(
        widths, delta["latent_tokens_read"]), "paged_attention_latent")
