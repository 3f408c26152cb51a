"""Share of the device's busy time inside the traced window spent in the
paged-attention kernel, both forms: self time of the operations whose
name starts with ``paged_attention`` (the ``name=`` of the kernel's
``pallas_call``: ``paged_attention_q1``, ``paged_attention_mq``) over
``busy_s``. Nothing is read where no operation carries the name."""

LAYER = "kernels"
MOVES = "out_tokens_per_s"
UNIT = "%"


def read(run):
    t = run.get("trace")
    if not t or not t.get("busy_s"):
        return None
    kernel = sum(s for name, s in t.get("op_self_s", {}).items()
                 if name.lstrip("%").startswith("paged_attention"))
    if kernel <= 0:
        return None
    return 100.0 * kernel / t["busy_s"]
