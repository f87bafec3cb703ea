"""Every metric reader on a run made by hand: the arithmetic of each, and
that a reader with nothing to read returns nothing."""

import types

import pytest

from harness import spec, xplane
from harness.runner import RunRecord


def _trace():
    ops = [("%fusion.1 = f32[] fusion()", 0.0, 40e6),
           ("%all-reduce.1 = f32[] all-reduce()", 40e6, 50e6),
           ("%fusion.1 = f32[] fusion()", 100e6, 140e6),
           ("%all-reduce.1 = f32[] all-reduce()", 140e6, 150e6),
           ("%fusion.1 = f32[] fusion()", 250e6, 290e6),
           ("%all-reduce.1 = f32[] all-reduce()", 290e6, 300e6)]
    modules = [("jit_step(1)", 0.0, 50e6), ("jit_step(1)", 100e6, 150e6),
               ("jit_step(1)", 250e6, 300e6)]
    return xplane.reduce_planes([xplane.Plane(
        "/device:TPU:0", {"XLA Ops": ops, "XLA Modules": modules})])


def _run(trace=None, chips=4, **edge_keys):
    first = {"t": 10.0, "images": 0, "attempted": 0, "steps": 0}
    last = {"t": 20.0, "images": 4000, "attempted": 100, "steps": 100}
    for key, (a, b) in edge_keys.items():
        first[key], last[key] = a, b
    cell = types.SimpleNamespace(traffic={})
    return RunRecord(
        cell=cell, chips=chips, setup_s=30.0, edges=(first, last),
        memory_peak_bytes=8_000_000_000, flops_per_image=1e9,
        images_per_device_step=10,
        peak={"bf16_flops_per_s": 1e12}, compile_s_in_setup=12.5,
        compiles_in_window=0, cache_misses=3, trace=trace)


def _read(kind, name, run):
    return spec.load_module(kind, name, spec.BENCH_DIR).read(run)


@pytest.mark.parametrize("kind, name, run, expected", [
    ("end_to_end", "images_per_s_per_chip", _run(), 100.0),
    ("end_to_end", "mfu", _run(), 0.1),
    ("end_to_end", "peak_hbm_gb", _run(), 8.0),
    ("end_to_end", "setup_s", _run(), 30.0),
    ("layer_metrics", "entry.compile_s", _run(), 12.5),
    ("layer_metrics", "entry.cache_misses", _run(), 3),
    ("layer_metrics", "trainer.compiles_in_window", _run(), 0),
    ("layer_metrics", "trainer.dispatch_ms",
     _run(dispatch_sum_s=(1.0, 1.5), dispatch_n=(10, 110)), 5.0),
    ("layer_metrics", "step.device_ms", _run(_trace()), 50.0),
    ("layer_metrics", "step_roofline", _run(_trace()), 20.0),
    ("layer_metrics", "device.idle_share", _run(_trace()), 0.5),
    ("layer_metrics", "device.step_period_ms_max", _run(_trace()), 150.0),
])
def test_each_reader_reads_what_it_says(kind, name, run, expected):
    assert _read(kind, name, run) == pytest.approx(expected)


@pytest.mark.parametrize("name, run", [
    ("trainer.dispatch_ms", _run()),                 # no such counter
    ("step.device_ms", _run()),
    ("step_roofline", _run()),
    ("device.idle_share", _run()),
    ("device.step_period_ms_max", _run()),
])
def test_a_reader_with_nothing_to_read_returns_nothing(name, run):
    assert _read("layer_metrics", name, run) is None
