"""Seconds of tracing, lowering and compiling (or loading from the compile
cache) during set-up, from ``jax.monitoring``'s duration events, summed over
the threads that compile."""

LAYER = "entry"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"
DRIVERS = None   # every driver
CHIPS = None     # any number of chips


def read(run):
    return run.compile_s_in_setup
