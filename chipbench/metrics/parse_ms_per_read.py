"""Self time of the ``store.parse`` span in the traced window, per read,
in ms: the reading threads parsing part files (one span a file)."""

SPAN = "store.parse"


def read(rec):
    """The metric from the window's record; None where it has none."""
    row = (rec["spans"] or {}).get(SPAN)
    if row is None or not rec["reads"]:
        return None
    return 1e3 * row["self_s"] / rec["reads"]
