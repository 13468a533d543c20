"""Self time of the ``store.h2d`` span in the traced window, per read,
in ms: host time inside each host-to-device transfer call."""

SPAN = "store.h2d"


def read(rec):
    """The metric from the window's record; None where it has none."""
    row = (rec["spans"] or {}).get(SPAN)
    if row is None or not rec["reads"]:
        return None
    return 1e3 * row["self_s"] / rec["reads"]
