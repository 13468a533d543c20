"""95th percentile of the latency of every read in the window."""

import numpy as np


def read(rec):
    """The metric from the window's record; None where it has none."""
    if not rec["latencies"]:
        return None
    return float(np.percentile(np.asarray(rec["latencies"]), 95)) * 1e3
