"""Peak bytes on the fullest of the cell's devices after the window:
``peak_bytes_in_use`` + ``peak_bytes_reserved`` (``harness/device.py`` says
why both)."""


def read(run):
    return run.memory_peak_bytes / 1e9
