"""Padding of the served union read, in % of the union's elements: the
elements the launch adds to bring each window's union to its bucket's
length (CacheStats gather_padded_elems) over the union elements the
windows read (cache.bytes_read at the 4-byte float32 itemsize).  Nothing
to read where the program keeps no gather_padded_elems."""

from harness.readers import ratio

ITEMSIZE = 4


def read(window):
    padded = window.counters.get("cache.gather_padded_elems")
    if padded is None:
        return None
    return ratio(padded, window.counters["cache.bytes_read"] / ITEMSIZE,
                 100.0)
