"""Time a step spends in the gradient exchange where nothing else can run:
the union, on device 0's serial ``XLA Ops`` line, of the events whose
instruction is a collective (``harness/xplane.py:COLLECTIVE``), clipped to
the traced window, over the step runs in it. ``None`` without a trace or
when the line holds no collective (one chip)."""

LAYER = "mesh"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh",)
CHIPS = (4,)


def read(run):
    if run.trace is None:
        return None
    from harness import xplane
    d = run.trace.devices[0]
    collectives = [(start, end) for name, start, end in d.ops
                   if xplane.COLLECTIVE.match(xplane.op_name(name))]
    if not collectives:
        return None
    return xplane.total(xplane.union(collectives, *d.window)) \
        / len(d.steps) / 1e6
