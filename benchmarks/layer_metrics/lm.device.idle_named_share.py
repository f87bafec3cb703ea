"""``device.idle_named_share`` for the cells of the token driver: the same reading
(``layer_metrics/device.idle_named_share.py``, whose entry lists the image cells), under a
name of its own because a reader declares its drivers."""

from harness import spec

LAYER = "device"
UNIT = "fraction"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_tokens",)
CHIPS = None


def read(run):
    return spec.load_module("layer_metrics", "device.idle_named_share",
                            run.cell.bench_dir).read(run)
