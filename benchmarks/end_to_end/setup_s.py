"""Process start to the first window edge: imports, reaching the chip, data,
weights, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
