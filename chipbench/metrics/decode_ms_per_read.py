"""Seconds the store spent unwrapping frames in the window, per read, in ms."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    if not rec["reads"]:
        return None
    return 1e3 * rec["io"]["decode_s"] / rec["reads"]
