"""Share of the traced window in which no operation ran on the device."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    trace = rec["trace"]
    if trace is None or trace.window_s <= 0 or not trace.devices:
        return None
    return 100.0 * trace.idle_share
