"""``compile_misses``: programs the persistent compile cache did not hold
when the window opened (``compile.jit_cache.stats()`` at the end of
set-up). A cold checkout reads the number of programs the cell compiles, a
warm one 0: this is a count, not a share, so 0 is a reading."""


def compute(trace, counters, run):
    return float(counters["compile_misses"])
