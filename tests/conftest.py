"""Test harness: 8 virtual CPU devices for multi-chip semantics tests.

The reference could only test multi-node behavior by deploying to AWS
(SURVEY.md §4); here a single process gets an 8-device CPU mesh. XLA_FLAGS
must be set before the first backend initialization. The suite runs on the
CPU backend whatever the host has: JAX_PLATFORMS is honoured, and the
jax.config line below covers code paths that read the config instead.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402 — env vars above must precede backend init

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def tiny_model():
    """A small ResNet-ish model for fast tests (full ResNet-18 is slow on CPU)."""
    from distributed_parameter_server_for_ml_training_tpu.models import ResNet

    def make(axis_name=None):
        return ResNet(stage_sizes=(1, 1), num_filters=8, num_classes=10,
                      axis_name=axis_name)

    return make


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def small_batch():
    r = np.random.default_rng(0)
    images = r.integers(0, 255, (16, 32, 32, 3), dtype=np.uint8)
    labels = (np.arange(16) % 10).astype(np.int32)
    return images, labels


#: a test of an accepted benchmark file that a later PR's cell makes false
#: and that PR may not edit (files under BENCHMARK.json's ``paths`` change
#: in ``benchmark`` PRs alone) -> why, and which test holds what is still
#: true of it
OVERTAKEN = {
    "tests/benchmark/test_bench_lm_cell.py::"
    "test_the_five_accepted_entries_list_the_image_cells_and_no_other":
        "its last line pins BENCHMARK.json to four cells ([1, 1, 4, 1] "
        "chips); PR 34 added the fifth. Everything else it asserts is held "
        "by tests/benchmark/test_bench_smallthinker_cell.py::"
        "test_the_accepted_entries_list_the_cells_they_listed; a benchmark "
        "PR should drop the line",
    "tests/benchmark/test_bench_smallthinker_cell.py::"
    "test_the_accepted_entries_list_the_cells_they_listed":
        "two of its lines pin BENCHMARK.json to five cells (the last cell's "
        "name, [1, 1, 4, 1, 1] chips); PR 36 added the sixth. Everything "
        "else it asserts is held by tests/benchmark/"
        "test_bench_nemotron_cell.py::"
        "test_the_accepted_entries_list_the_cells_they_listed; a benchmark "
        "PR should drop the two lines",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        reason = OVERTAKEN.get(item.nodeid)
        if reason:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
