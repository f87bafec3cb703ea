"""The trace reduction: interval arithmetic that a sum would get wrong, and
the recorded trace of a few ViT-Tiny steps on the v5e."""

import os

import pytest

from harness import xplane
from harness.xplane import Plane

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "vit_tiny_sync_v5e.xplane.pb")


@pytest.mark.parametrize("intervals, clip, expected", [
    ([(0, 10), (5, 15)], (None, None), [(0, 15)]),             # overlapping
    ([(0, 100), (10, 20), (30, 40)], (None, None), [(0, 100)]),  # nested
    ([(30, 40), (0, 10), (10, 20)], (None, None), [(0, 20), (30, 40)]),
    ([(0, 10), (0, 10), (0, 10)], (None, None), [(0, 10)]),    # one span x3
    ([(0, 10), (20, 30)], (5, 25), [(5, 10), (20, 25)]),       # clipped
    ([(0, 10)], (20, 30), []),                                 # outside
    ([], (0, 10), []),
])
def test_union(intervals, clip, expected):
    assert xplane.union(intervals, *clip) == expected
    assert xplane.total(xplane.union(intervals, *clip)) == sum(
        e - s for s, e in expected)


def test_gaps_are_what_the_union_leaves():
    covered = xplane.union([(2, 4), (3, 6), (8, 9)])
    assert xplane.gaps(covered, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    assert xplane.gaps([], 0, 10) == [(0, 10)]
    assert xplane.gaps([(0, 10)], 0, 10) == []


def test_self_time_does_not_charge_a_loop_its_body():
    events = [("while", 0, 100), ("fusion", 10, 30), ("fusion", 40, 80),
              ("copy", 50, 60), ("fusion", 120, 130)]
    times = xplane.self_times(events)
    assert times == pytest.approx({"while": 40e-9, "fusion": 60e-9,
                                   "copy": 10e-9})
    assert sum(times.values()) == pytest.approx(
        xplane.total(xplane.union((s, e) for _n, s, e in events)) / 1e9)


@pytest.mark.parametrize("text, name, kind", [
    ("%fusion.565 = (bf16[768]{0}, bf16[64,65,768]{2,1,0}) fusion(bf16[64] "
     "%convolution_add_fusion.3), kind=kOutput, calls=%fused_computation.872",
     "fusion.565", "fusion"),
    ("%all-reduce-start.3 = f32[10]{0} all-reduce-start(f32[10]{0} %x)",
     "all-reduce-start.3", "all-reduce-start"),
    ("%copy = f32[2]{0} copy(f32[2]{0} %p)", "copy", "copy"),
    ("plain-name", "plain-name", "plain-name"),
])
def test_op_names_are_cut_from_the_hlo_text(text, name, kind):
    assert xplane.op_name(text) == name
    assert xplane.op_kind(text) == kind


@pytest.mark.parametrize("name, is_collective", [
    ("all-reduce", True), ("all-reduce.12", True),
    ("all-reduce-start.1", True), ("all-gather-done", True),
    ("reduce-scatter.4", True), ("collective-permute-start.9", True),
    ("all-to-all", True), ("fusion.3", False), ("copy-start.1", False),
    ("all-reduce-scatter_fusion", False)])
def test_which_instructions_count_as_collectives(name, is_collective):
    assert bool(xplane.COLLECTIVE.match(name)) == is_collective


def synthetic(n_devices: int = 1) -> list[Plane]:
    """Two steps a device; ops overlap, nest and repeat on other lines, so
    that summing durations, or summing lines, would pass the window."""
    planes = []
    for d in range(n_devices):
        shift = 7 * d
        planes.append(Plane(f"/device:TPU:{d}", {
            "Steps": [("0", 100 + shift, 400 + shift)],
            "XLA Modules": [
                ("jit_warm(1)", 0 + shift, 50 + shift),
                ("jit_step(22)", 100 + shift, 200 + shift),
                ("jit_eval(3)", 209 + shift, 220 + shift),
                ("jit_step(22)", 300 + shift, 400 + shift)],
            "XLA Ops": [
                ("%x = f32[] add()", 0 + shift, 50 + shift),  # before window
                ("%while.1 = () while()", 100 + shift, 200 + shift),
                ("%fusion.1 = f32[] fusion()", 110 + shift, 150 + shift),
                ("%all-reduce.1 = f32[] all-reduce()", 150 + shift,
                 190 + shift),
                ("%fusion.2 = f32[] fusion()", 210 + shift, 220 + shift),
                ("%fusion.1 = f32[] fusion()", 300 + shift, 360 + shift),
                ("%all-reduce.1 = f32[] all-reduce()", 360 + shift,
                 400 + shift),
                ("%late = f32[] add()", 390 + shift, 450 + shift)],
            "Async XLA Ops": [("%copy-start.1 = () copy-start()",
                               100 + shift, 400 + shift)],
        }))
    return planes


def test_reduction_of_overlapping_nested_multi_line_events():
    r = xplane.reduce_planes(synthetic())
    assert r.step_module == "jit_step"
    d = r.devices[0]
    assert d.window == (100, 400)
    assert d.busy_ns == 100 + 10 + 100           # the union, clipped
    assert 0 < r.busy_s <= r.window_s
    naive = sum(e - s for _n, s, e in d.ops)     # what a sum would say
    assert naive > d.window[1] - d.window[0]
    assert r.step_device_ms() == pytest.approx(100 / 1e6)
    b = r.breakdown()
    assert dict(map(tuple, b["device_ops"])) == pytest.approx(
        {"while": 20e-9, "fusion": 110e-9, "all-reduce": 70e-9,
         "late": 10e-9})
    assert sum(s for _n, s in b["idle_gaps"]) == pytest.approx(
        (r.window_s - r.busy_s))


def test_idle_gaps_are_named_by_the_program_the_device_ran_next():
    planes = synthetic()
    for p in planes:          # stretch the clock: gaps above the short limit
        for line, events in p.lines.items():
            p.lines[line] = [(n, s * 1e4, e * 1e4) for n, s, e in events]
    b = xplane.reduce_planes(planes).breakdown()
    # 200-210 ends where jit_eval's first op starts (the module opened a
    # little earlier); 220-300 ends at the next jit_step
    assert dict(map(tuple, b["idle_gaps"])) == pytest.approx(
        {"before:jit_eval": 10e-5, "before:jit_step": 80e-5})


def test_four_devices_report_the_mean_and_never_the_sum():
    one = xplane.reduce_planes(synthetic(1))
    four = xplane.reduce_planes(synthetic(4))
    assert len(four.devices) == 4
    assert four.busy_s == pytest.approx(one.busy_s)
    assert four.window_s == pytest.approx(one.window_s)
    assert four.busy_s <= four.window_s


@pytest.mark.parametrize("planes, why", [
    ([Plane("/host:CPU", {"t": [("PjitFunction(f)", 0, 1)]})],
     "no device plane"),
    ([], "no plane at all"),
    ([Plane("/device:TPU:0", {})], "no events"),
    ([Plane("/device:TPU:0", {"XLA Ops": [("%a = add()", 0, 1)]})],
     "no module"),
    ([Plane("/device:TPU:0", {"XLA Ops": [("%a = add()", 0, 1)],
                              "XLA Modules": [("jit_step(1)", 0, 1)]})],
     "one step is no window"),
    ([Plane("/device:TPU:0", {"XLA Ops": [("%a = add()", 500, 600)],
                              "XLA Modules": [("jit_step(1)", 0, 1),
                                              ("jit_step(1)", 2, 3)]})],
     "no op inside the window: busy would be 0"),
])
def test_a_trace_with_nothing_to_read_is_an_error(planes, why):
    with pytest.raises(xplane.TraceError):
        xplane.reduce_planes(planes)


def test_find_xplane_reports_an_empty_directory(tmp_path):
    with pytest.raises(xplane.TraceError):
        xplane.find_xplane(str(tmp_path))


@pytest.fixture(scope="module")
def recorded():
    planes = xplane.load_planes(FIXTURE)
    return planes, xplane.reduce_planes(planes)


def test_the_fixture_is_small():
    assert os.path.getsize(FIXTURE) < 1 << 20


def test_recorded_trace_has_the_planes_and_lines_the_reduction_names(
        recorded):
    planes, _r = recorded
    device = next(p for p in planes if p.name == "/device:TPU:0")
    assert {"XLA Modules", "XLA Ops"} <= set(device.lines)
    assert [p.name for p in planes] == ["/device:TPU:0"]   # host not read


def test_recorded_trace_reduces_to_a_window_that_holds_its_busy_time(
        recorded):
    _planes, r = recorded
    assert r.step_module == "jit_worker_step"
    d = r.devices[0]
    assert len(d.steps) == 12                      # two epochs of six steps
    assert 0 < r.busy_s <= r.window_s
    # twelve steps of 0.902 ms and two evaluations in a 66.7 ms window
    assert r.step_device_ms() == pytest.approx(0.902, abs=0.002)
    assert r.window_s == pytest.approx(0.066654, abs=1e-5)
    assert r.busy_s == pytest.approx(0.010924, abs=1e-5)
    # what PR 23 was refused for: every line summed passes the window
    everything = sum(e - s for p in _planes if p.name == "/device:TPU:0"
                     for ev in p.lines.values() for _n, s, e in ev) / 1e9
    assert everything > r.window_s


def test_recorded_trace_breakdown_is_in_the_contracts_form(recorded):
    from harness import validate
    _planes, r = recorded
    b = r.breakdown()
    assert validate._check_breakdown(b) == []
    assert 1 <= len(b["device_ops"]) <= 10 and b["idle_gaps"]
    assert all(" = " not in name for name, _s in b["device_ops"])
    assert sum(s for _n, s in b["idle_gaps"]) <= r.window_s - r.busy_s + 1e-9
    names = {name for name, _s in b["idle_gaps"]}
    assert {"before:jit_eval_step", "before:jit_worker_step"} <= names
    s = xplane.summary(_planes, r)
    assert s["step_module"] == "jit_worker_step" and s["steps_in_window"] == [12]
