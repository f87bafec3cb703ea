"""``device.step_period_ms_max`` for the cells of the token driver: the same reading
(``layer_metrics/device.step_period_ms_max.py``, whose entry lists the image cells), under a
name of its own because a reader declares its drivers."""

from harness import spec

LAYER = "device"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_tokens",)
CHIPS = None


def read(run):
    return spec.load_module("layer_metrics", "device.step_period_ms_max",
                            run.cell.bench_dir).read(run)
