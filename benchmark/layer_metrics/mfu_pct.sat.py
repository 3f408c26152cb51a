"""Model FLOP/s utilisation of the whole serving step over the window: the
forward operations the served tokens needed (``serve_flops_per_token`` of
the family's model file, ``models/<family>.py``: two a matmul parameter a
token meets on this chip, plus the absorbed attention over its cache)
over ``window_s`` times the chip's published bf16 peak. Tokens:
``decode_tokens`` (each with its logits) and ``prefill_tokens`` (without)
of the window; the attention's part from ``latent_tokens_read`` (cache
tokens read, summed over lanes and layers), which is what the mean cache
length a token saw is worked out from. Lanes that carried no token are no
work: this is the share of the peak that did something a client asked
for. The whole-step share a gain claimed in this cell is bounded by."""

import os

LAYER = "engine step"
MOVES = "out_tokens_per_s"
UNIT = "%"


def read(run):
    import jax

    from benchmark import harness
    from benchmark.flops import peaks
    if run.get("platform") != "tpu":
        return None
    shared = harness.load_by_name(
        "layer_metrics", "moe_grouped_roofline_pct.sat",
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))])
    got = shared.family_counts(run)
    if got is None:
        return None
    model, widths, delta = got
    decode = delta.get("decode_tokens", 0)
    prefill = delta.get("prefill_tokens", 0)
    if not hasattr(model, "serve_flops_per_token") or decode + prefill <= 0:
        return None
    # serve_flops_per_token is linear in the cache length, so the tokens'
    # mean cache length gives the sum: cache tokens read a layer, a token
    context = (delta.get("latent_tokens_read", 0) /
               widths["num_hidden_layers"] / (decode + prefill))
    flops = (decode * model.serve_flops_per_token(widths, context) +
             prefill * model.serve_flops_per_token(widths, context,
                                                   head=False))
    peak = peaks(run.get("device_kind") or
                 jax.devices()[0].device_kind)["bf16_flops"]
    return 100.0 * flops / (run["window_s"] * peak)
