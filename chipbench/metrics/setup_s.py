"""Seconds from process start to the first timed read."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    return rec["setup_s"]
