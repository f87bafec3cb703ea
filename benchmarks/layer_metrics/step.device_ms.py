"""Median device duration of the train-step module's runs in the traced
slice (the module that takes most of the device's time: the mesh step, or a
worker's gradient step)."""

LAYER = "step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = None
CHIPS = None


def read(run):
    return None if run.trace is None else run.trace.step_device_ms()
