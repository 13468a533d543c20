"""Programs compiled or loaded from the persistent cache in the window."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    return rec["compiles"]
