"""Backend compilations (or cache loads) between the window's edges, from
``jax.monitoring``. Anything but 0 makes the run incorrect."""

LAYER = "trainer"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "images_per_s_per_chip"
DRIVERS = None
CHIPS = None


def read(run):
    return run.compiles_in_window
