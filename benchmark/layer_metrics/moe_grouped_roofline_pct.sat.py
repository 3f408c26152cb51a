"""The routed experts' grouped matmuls against the chip's roofline: the
time the kernels' operations and bytes over the measured window would take
at the published peaks (the larger of the two), over the device seconds
the kernels took.

Operations and bytes: ``grouped_matmul_counts`` of the family's model file
(``models/<family>.py``) on the window's counter deltas: ``moe_pairs_local``
rows in ``moe_expert_calls`` (layer, expert) calls, with the widths the
engine reports in ``stats()["model"]``. Device seconds: the kernels' self
time inside the TRACED slice (operations named ``moe_grouped_matmul*``),
scaled by ``window_s`` over the slice's length. That assumes the traced
seconds are like the rest of the window: the counters run over the whole
window, the trace over its last seconds."""

LAYER = "kernels"
MOVES = "out_tokens_per_s"
UNIT = "%"


def family_counts(run):
    """``(model file, widths, counter deltas)`` of a serving run whose
    engine describes its model, else None."""
    import os

    from benchmark import harness
    a, b = run.get("stats_before"), run.get("stats_after")
    if not a or not b or not b.get("model") or "spans" not in b:
        return None
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        model = harness.load_by_name("models", b["model"]["family"], [here])
    except FileNotFoundError:
        return None
    ca, cb = a["spans"]["counters"], b["spans"]["counters"]
    return model, b["model"], {k: v - ca.get(k, 0) for k, v in cb.items()}


def roofline_pct(run, counts, prefix):
    """``counts`` (operations and bytes over the window) against the
    peaks, over the scaled device seconds of the operations ``prefix*``."""
    import jax

    from benchmark.flops import peaks
    t = run.get("trace")
    if run.get("platform") != "tpu" or not t or not t.get("window_s"):
        return None
    seconds = sum(s for name, s in t.get("op_self_s", {}).items()
                  if name.lstrip("%").startswith(prefix))
    if seconds <= 0:
        return None
    seconds *= run["window_s"] / t["window_s"]
    peak = peaks(run.get("device_kind") or jax.devices()[0].device_kind)
    ideal = max(counts["flops"] / peak["bf16_flops"],
                counts["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * ideal / seconds


def read(run):
    got = family_counts(run)
    if got is None:
        return None
    model, widths, delta = got
    if not hasattr(model, "grouped_matmul_counts") or \
            delta.get("moe_pairs_local", 0) <= 0:
        return None
    return roofline_pct(run, model.grouped_matmul_counts(
        widths, delta["moe_pairs_local"], delta["moe_expert_calls"]),
        "moe_grouped_matmul")
