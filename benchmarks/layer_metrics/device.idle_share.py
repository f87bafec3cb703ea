"""1 - busy_s / window_s of the traced slice, mean over the cell's devices:
the share of the time in which no instruction ran on the chip."""

LAYER = "device"
UNIT = "fraction"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = None
CHIPS = None


def read(run):
    if run.trace is None:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
