"""Block-cache hits over lookups in the window, in percent."""


def read(rec):
    """The metric from the window's record; None where it has none."""
    io = rec["io"]
    looked = io["cache_hits"] + io["cache_misses"]
    if not looked:
        return None
    return 100.0 * io["cache_hits"] / looked
