"""Frame bytes decoded in the window over the bytes delivered to HBM."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    if not rec["bytes"]:
        return None
    return rec["io"]["frame_bytes_decoded"] / rec["bytes"]
