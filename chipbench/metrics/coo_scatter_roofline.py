"""Least time for the reads' device work over the device's busy time, in %.

The least time is the HBM traffic the scatters must make at the least
(the dense output written once, one int32 index and one value read per
non-zero; ``Built.kernel_bytes``) over the chip's HBM peak. Busy time is
the union of every device operation in the traced window, whatever its
name, so the share reads the same work whatever implements the scatter.
"""


def read(rec):
    """The metric from the window's record; None where it has none."""
    trace = rec["trace"]
    need = rec["kernel_bytes"]
    if trace is None or trace.busy_s <= 0 or not need or None in need:
        return None
    least_s = sum(need) / rec["peaks"]["hbm_bytes_per_s"]
    if least_s <= 0:
        return None
    return 100.0 * least_s / trace.busy_s
