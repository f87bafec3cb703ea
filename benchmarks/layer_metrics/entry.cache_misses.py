"""New files in the persistent compile cache during this run: programs that
compiled instead of loading. 0 on every run of a cell after its first."""

LAYER = "entry"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"
DRIVERS = None
CHIPS = None


def read(run):
    return run.cache_misses
