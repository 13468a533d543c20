"""Self time of the ``store.undelta`` span in the traced window (a delta
frame's base fetched and XORed back), per gateway load run, in ms."""

SPAN = "store.undelta"


def read(rec):
    """The metric from the window's record; None where it has none."""
    row = (rec["spans"] or {}).get(SPAN)
    loads = rec["counters"].get("loads_run")
    if row is None or not loads:
        return None
    return 1e3 * row["self_s"] / loads
