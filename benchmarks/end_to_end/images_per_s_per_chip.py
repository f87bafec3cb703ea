"""Training images whose update is complete on the device between the two
window edges, over the time between the edges, over the cell's chips."""


def read(run):
    return run.images_per_s_per_chip
