"""The longest time between successive starts of the train-step module on
device 0 in the traced slice: a step plus the epoch end's stall, since the
slice holds at least one epoch end. (A percentile of the periods would need
a hundred steps or more in the slice; no cell's slice holds them.)"""

LAYER = "device"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh",)
CHIPS = None


def read(run):
    if run.trace is None:
        return None
    starts = run.trace.step_starts_ms()
    if len(starts) < 2:
        return None
    return max(b - a for a, b in zip(starts, starts[1:]))
