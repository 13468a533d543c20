"""Bytes on disk under the store's root over the logical bytes written."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    return rec["stored_bytes"] / rec["logical_bytes"]
