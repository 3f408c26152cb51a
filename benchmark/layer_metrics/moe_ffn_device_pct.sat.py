"""Share of the device's busy time inside the traced window spent in the
routed experts' grouped matmuls: self time of the operations whose name
starts with ``moe_grouped_matmul`` (the ``name=`` of the kernel's
``pallas_call``: ``moe_grouped_matmul_gated`` makes gate and up,
``moe_grouped_matmul`` the down projection) over ``busy_s``. Nothing is
read where no operation carries the name."""

LAYER = "kernels"
MOVES = "out_tokens_per_s"
UNIT = "%"


def kernel_seconds(trace, prefix):
    """Self seconds, inside the traced window, of the operations named
    ``prefix*``."""
    return sum(s for name, s in trace.get("op_self_s", {}).items()
               if name.lstrip("%").startswith(prefix))


def read(run):
    t = run.get("trace")
    if not t or not t.get("busy_s"):
        return None
    kernel = kernel_seconds(t, "moe_grouped_matmul")
    if kernel <= 0:
        return None
    return 100.0 * kernel / t["busy_s"]
