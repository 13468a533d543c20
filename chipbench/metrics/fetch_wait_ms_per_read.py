"""Seconds reading threads waited in the window for files not yet fetched
and decoded (``ReadStats.fetch_wait_s``), per read, in ms."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    wait = rec["io"].get("fetch_wait_s")
    if wait is None or not rec["reads"]:
        return None
    return 1e3 * wait / rec["reads"]
