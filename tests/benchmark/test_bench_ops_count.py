"""Operation and parameter counts from shapes, pinned to the totals the
driver's PR 22 medians imply and to the models the registry builds."""

import json
import os

import pytest

from harness import spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _counter(config):
    return spec.load_module("ops_count", config["ops_count"], spec.BENCH_DIR)


@pytest.mark.parametrize("name, gflop, gmac, params", [
    ("resnet18-cifar100", 3.333, 0.5554688, 11_220_132),
    ("vit-b16-224", 105.4, 17.563828224, 86_567_656),
])
def test_counts_match_the_anchors(name, gflop, gmac, params):
    config = _config(name)
    counter = _counter(config)
    arch = config["architecture"]
    assert counter.train_flops_per_image(arch) / 1e9 == pytest.approx(
        gflop, rel=0.01)
    assert counter.forward_macs(arch) / 1e9 == pytest.approx(gmac, rel=1e-9)
    assert counter.train_flops_per_image(arch) == 6 * counter.forward_macs(
        arch)
    assert counter.parameter_count(arch) == params


@pytest.mark.parametrize("name", ["resnet18-cifar100", "vit-b16-224"])
def test_parameter_count_is_the_registrys_model(name):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_parameter_server_for_ml_training_tpu.models import (
        get_model)
    config = _config(name)
    arch = config["architecture"]
    model = get_model(config["model"], num_classes=arch["num_classes"],
                      image_size=arch["image_size"])
    size = arch["image_size"]
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3)), train=False))
    leaves = jax.tree_util.tree_leaves(shapes["params"])
    assert sum(int(np.prod(x.shape)) for x in leaves) \
        == _counter(config).parameter_count(arch)


def test_resnet50_imagenet_shapes_count_too():
    """The bottleneck / ImageNet-stem branch, for the cell PERF.md keeps for
    later: He et al. give 3.8 GFLOPs (multiply-adds) for the 50-layer net."""
    counter = spec.load_module("ops_count", "resnet", spec.BENCH_DIR)
    arch = {"image_size": 224, "stem": "imagenet7x7", "block": "bottleneck",
            "stage_sizes": [3, 4, 6, 3], "stage_widths": [64, 128, 256, 512],
            "num_classes": 1000}
    assert counter.forward_macs(arch) / 1e9 == pytest.approx(4.09, rel=0.02)
    assert counter.parameter_count(arch) == pytest.approx(25.56e6, rel=0.01)


def test_an_unknown_stem_is_an_error():
    counter = spec.load_module("ops_count", "resnet", spec.BENCH_DIR)
    with pytest.raises(ValueError):
        counter.forward_macs({"image_size": 32, "stem": "other",
                              "stage_widths": [64]})
