"""Seconds the window's gateway jobs waited between submission and
dispatch (``Gateway.stats()["queue_wait_s"]``), per load run, in ms."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    wait = rec["counters"].get("queue_wait_s")
    loads = rec["counters"].get("loads_run")
    if wait is None or not loads:
        return None
    return 1e3 * wait / loads
