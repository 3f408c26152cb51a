"""Peak device memory in use on the fullest chip of the cell
(``memory_stats()["peak_bytes_in_use"]``), in 1e9 bytes."""

LAYER = "train step"
MOVES = "train_tokens_per_s"
UNIT = "GB"


def read(run):
    if not run.get("memory_peak_bytes"):
        return None
    return run["memory_peak_bytes"] / 1e9
