"""``trainer.epoch_end_host_ms`` for the cells of the token driver: the same reading
(``layer_metrics/trainer.epoch_end_host_ms.py``, whose entry lists the image cells), under a
name of its own because a reader declares its drivers."""

from harness import spec

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_tokens",)
CHIPS = None


def read(run):
    return spec.load_module("layer_metrics", "trainer.epoch_end_host_ms",
                            run.cell.bench_dir).read(run)
