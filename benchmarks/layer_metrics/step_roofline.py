"""The step program's share of its roofline, in percent: the least time the
chip could take for one step's forward and backward operations (from shapes,
``ops_count/``) at its bf16 peak, over ``step.device_ms``. Compute-bound by
that bound: the operations' time at peak is far above the bytes' time at the
memory peak for both model families here. No Pallas kernel runs in these
cells; a kernel's own share comes with the cell that runs it."""

LAYER = "step"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "mfu"
DRIVERS = None
CHIPS = None


def read(run):
    if run.trace is None:
        return None
    least_s = (run.flops_per_image * run.images_per_device_step
               / run.peak["bf16_flops_per_s"])
    return 100.0 * least_s / (run.trace.step_device_ms() / 1e3)
