"""Union elements per union run: the elements the windows' unions read
(cache.bytes_read at the 4-byte float32 itemsize) over the runs of
those unions (CacheStats union_runs).  How long a stretch the union
build and the slicing handle at once.  Nothing to read where the
program keeps no union_runs, or where no union was read."""

from harness.readers import ratio

ITEMSIZE = 4


def read(window):
    runs = window.counters.get("cache.union_runs")
    if runs is None:
        return None
    elems = window.counters["cache.bytes_read"] / ITEMSIZE
    return ratio(elems, runs) if elems > 0 else None
