"""Model FLOP/s utilisation: the operations a token needs forward and
backward (``flops.train_flops_per_token``; recomputation not counted, the
embedding gather not counted) times tokens per second, over the chips used
times the chip's published bf16 peak. Only on a device in the peaks table."""

LAYER = "train step"
MOVES = "train_tokens_per_s"
UNIT = "%"


def read(run):
    if run.get("platform") != "tpu" or "tokens_per_s" not in run:
        return None
    from benchmark.flops import peaks
    peak = peaks(run["device_kind"])["bf16_flops"] * run["chips"]
    return 100.0 * run["flops_per_token"] * run["tokens_per_s"] / peak
