"""Share of the window's load requests that joined a load already in
flight rather than running their own, in %."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    joined = rec["counters"].get("coalesced")
    requests = rec["counters"].get("requests")
    if joined is None or not requests:
        return None
    return 100.0 * joined / requests
