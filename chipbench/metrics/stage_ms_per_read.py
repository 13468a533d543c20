"""Self time of the ``store.stage`` span in the traced window, per read,
in ms: a read's host staging (copies into the staging buffer; COO
concatenation, slicing and flat indices)."""

SPAN = "store.stage"


def read(rec):
    """The metric from the window's record; None where it has none."""
    row = (rec["spans"] or {}).get(SPAN)
    if row is None or not rec["reads"]:
        return None
    return 1e3 * row["self_s"] / rec["reads"]
