"""The benchmark's data: class-template images made from the seed.

The construction is ``data/cifar.py:synthetic_cifar100`` /
``synthetic_imagenet``'s (a smooth random colour template per class plus
pixel noise, which a model separates within an epoch), kept here so that the
yardstick's inputs cannot change under it, and made chunk by chunk so that
no float32 copy of the whole set exists: ``distinct`` images are generated
and tiled to the epoch length.
"""

from __future__ import annotations

import numpy as np

_CHUNK_BYTES = 64 << 20


def class_template_arrays(*, image_size: int, num_classes: int, n_train: int,
                          n_test: int, seed: int, coarse_px: int,
                          template_amp: float, noise: float,
                          distinct_train: int | None = None):
    """(x_train, y_train, x_test, y_test): uint8 NHWC images, int32 labels."""
    if image_size % coarse_px:
        raise ValueError(f"image size {image_size} is not a multiple of the "
                         f"template's {coarse_px} px")
    rep = image_size // coarse_px
    coarse = np.random.default_rng([seed, 0]).standard_normal(
        (num_classes, coarse_px, coarse_px, 3), dtype=np.float32)
    chunk = max(1, _CHUNK_BYTES // (image_size * image_size * 3 * 4))

    def split(n: int, tag: int):
        r = np.random.default_rng([seed, tag])
        y = (np.arange(n) % num_classes).astype(np.int32)
        r.shuffle(y)
        x = np.empty((n, image_size, image_size, 3), np.uint8)
        for lo in range(0, n, chunk):
            t = coarse[y[lo:lo + chunk]].repeat(rep, axis=1).repeat(rep,
                                                                    axis=2)
            t = 0.5 + template_amp * t
            t += noise * r.standard_normal(t.shape, dtype=np.float32)
            x[lo:lo + chunk] = np.clip(t, 0.0, 1.0) * 255.0
        return x, y

    distinct = min(distinct_train or n_train, n_train)
    x_tr, y_tr = split(distinct, 1)
    if distinct < n_train:
        reps = -(-n_train // distinct)
        x_tr = np.tile(x_tr, (reps, 1, 1, 1))[:n_train]
        y_tr = np.tile(y_tr, reps)[:n_train]
    x_te, y_te = split(n_test, 2)
    return x_tr, y_tr, x_te, y_te


def make_dataset(config: dict, n_train: int, seed: int):
    """The program's ``Dataset`` for a configuration file and a length."""
    from distributed_parameter_server_for_ml_training_tpu.data.cifar import (
        Dataset)
    data, arch = config["data"], config["architecture"]
    if data["kind"] != "class_template":
        raise ValueError(f"unknown data kind {data['kind']!r}")
    x_tr, y_tr, x_te, y_te = class_template_arrays(
        image_size=int(arch["image_size"]),
        num_classes=int(arch["num_classes"]), n_train=n_train,
        n_test=int(config["eval_images"]), seed=seed,
        coarse_px=int(data["coarse_px"]),
        template_amp=float(data["template_amp"]),
        noise=float(data["noise"]),
        distinct_train=data.get("distinct_train_images"))
    return Dataset(x_tr, y_tr, x_te, y_te,
                   num_classes=int(arch["num_classes"]), synthetic=True)
