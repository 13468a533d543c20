"""Seconds frames off the wire waited in the window for a decode worker
(``ReadStats.decode_queue_s``), per read, in ms."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    wait = rec["io"].get("decode_queue_s")
    if wait is None or not rec["reads"]:
        return None
    return 1e3 * wait / rec["reads"]
