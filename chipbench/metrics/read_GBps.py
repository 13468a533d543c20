"""Bytes of every read that landed in HBM, over the window's seconds."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    if rec["window_s"] <= 0 or not rec["reads"]:
        return None
    return rec["bytes"] / rec["window_s"] / 1e9
