"""The readers of the program's phase spans, and the clock join, on spans
and device intervals made by hand: a known offset is found again within
the stated width, a broken ordering or a miscounted epoch gives nothing,
idle time is shared out exactly. Then the committed benchmark with the new
entries, and the two ``program_span`` readers on the recorder an in-process
run of ``SyncTrainer`` leaves (CPU: the numbers are thrown away)."""

import json
import os
import types

import pytest

from harness import hostspans, spec, validate, xplane
from harness.runner import RunRecord

STEP, EVAL = "jit_worker_step(77)", "jit_eval_step(78)"
OFFSET = 12_345.678          # host monotonic seconds - device seconds
LAUNCH = 0.0003              # dispatch to the run's start on an idle device
TRANSFER = 0.014             # a training batch's way to the device
READY = 0.0001               # device done to block_until_ready's return
JITTER = 0.00002             # by which an epoch's READY may be longer
FETCH = 0.0005               # device done to a scalar on the host
STEPS = 4


def _timeline(first=5, epochs=3, *, in_trace=2.5, tail_steps=2):
    """Spans of epochs ``first - 1`` .. ``first + epochs`` as the trainer
    records them, and the device's module runs of the last ``in_trace``
    epochs before the last edge plus ``tail_steps`` runs after it. Steps
    take 100 ms on the device and 1 ms to dispatch, so the host runs an
    epoch ahead and waits at its end; a run on an idle device starts when
    its input has arrived."""
    spans, runs, edges = [], [], {}
    h, free = 100.0, 0.0          # host clock; when the device falls idle

    def span(name, start, stop, epoch, **attrs):
        spans.append({"name": name, "mono": start, "dur": stop - start,
                      "attrs": dict(attrs, epoch=epoch)})

    def run(name, ready, seconds):
        nonlocal free
        start = max(ready + LAUNCH, free)
        free = start + seconds
        runs.append((name, (start - OFFSET) * 1e9, (free - OFFSET) * 1e9))
        return free

    for e in range(first - 1, first + epochs + 1):
        begun = h
        for i in range(STEPS):
            span("trainer.input", h, h + 0.004, e, step=e * STEPS + i)
            h += 0.004
            span("trainer.step", h, h + 0.001, e, step=e * STEPS + i)
            run(STEP, h + TRANSFER, 0.100)
            h += 0.001
        ready = free + READY + JITTER * (e % 3)
        span("trainer.epoch_sync", h, ready + 0.0005, e, ready_mono=ready)
        h = ready + 0.0005
        done = run(EVAL, h + 0.002, 0.010) + FETCH
        span("trainer.eval", h, done, e, batches=1)
        edges[e + 1] = {"t": done + 0.001, "epochs": e + 1,
                        "steps": (e + 1) * STEPS}
        span("trainer.epoch_report", done, done + 0.003, e)
        h = done + 0.003
        span("trainer.epoch", begun, h, e, first_step=e * STEPS)
    last = first + epochs         # epochs ended at the last edge
    keep = int(in_trace * (STEPS + 1))
    before = [r for r in runs if r[2] <= (edges[last]["t"] - OFFSET) * 1e9]
    after = [r for r in runs if r not in before][:tail_steps]
    return spans, before[-keep:] + after, (edges[first], edges[last])


def _run(spans_edges_runs, chips=1):
    _spans, runs, edges = spans_edges_runs
    trace = None
    if runs:
        trace = xplane.reduce_planes([xplane.Plane("/device:TPU:0", {
            "XLA Ops": [("%fusion.1 = f32[] fusion()", s, e)
                        for _n, s, e in runs],
            "XLA Modules": runs})])
    return RunRecord(
        cell=types.SimpleNamespace(traffic={}), chips=chips, setup_s=30.0,
        edges=edges, memory_peak_bytes=1, flops_per_image=1.0,
        images_per_device_step=1, peak={}, compile_s_in_setup=0.0,
        compiles_in_window=0, cache_misses=0, trace=trace)


@pytest.fixture
def recorder():
    """The program's recorder, empty, for a test to fill by hand."""
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_recorder)
    rec = get_recorder()
    rec.clear()
    yield rec
    rec.clear()


def _reader(name):
    return spec.load_module("layer_metrics", name, spec.BENCH_DIR)


def _join(spans, runs, edges, log=lambda _m: None):
    run = _run((spans, runs, edges))
    return hostspans.join_run(run, hostspans.by_epoch(spans), log)


# -- the clock join -----------------------------------------------------------

@pytest.mark.parametrize("in_trace, tail_steps", [
    (2.5, 2),      # two evaluations and a cut epoch before them
    (0.7, 0),      # one evaluation and the two queued steps before it
    (1.0, 3),      # exactly one epoch
    (3.0, 0)])
def test_a_known_offset_is_found_within_the_stated_width(in_trace,
                                                         tail_steps):
    join = _join(*_timeline(in_trace=in_trace, tail_steps=tail_steps))
    # the estimate is the tightest wait: late by what a wait trails the
    # device, and the truth lies within the stated width below it
    assert OFFSET + READY - 1e-9 <= join.offset_s \
        <= OFFSET + READY + 2 * JITTER + 1e-9
    assert join.offset_s - join.width_s <= OFFSET <= join.offset_s
    # the tightest start is the evaluation's: its span began 2 ms of input
    # and a launch before its run
    assert join.width_s == pytest.approx(
        join.offset_s - OFFSET + 0.002 + LAUNCH, abs=1e-6)
    assert 0.0 <= join.agreement_s <= 2 * JITTER + 1e-9
    assert join.to_host(1e9) == pytest.approx(1.0 + join.offset_s)


def test_the_epochs_waits_say_how_well_they_agree():
    join = _join(*_timeline(in_trace=3.0, tail_steps=0))
    assert join.agreement_s == pytest.approx(2 * JITTER, abs=1e-7)


def test_the_join_says_what_it_found():
    said = []
    _join(*_timeline(), log=said.append)
    assert len(said) == 1 and "tightest of 3 epochs' waits" in said[0]
    assert "agree within 0.040 ms" in said[0]
    assert "would allow 2.400 ms less" in said[0]


def _late_fetch(spans, runs, edges):
    """An evaluation whose host span ends before the device run did."""
    span = [s for s in spans if s["name"] == "trainer.eval"][-2]
    span["dur"] -= 0.004


def _early_run(spans, runs, edges):
    """A step run that starts before the host dispatched it."""
    i = next(i for i, r in enumerate(runs) if r[0] == EVAL) + 1
    runs[i] = (runs[i][0], runs[i][1] - 20e6, runs[i][2])


def _lost_step(spans, runs, edges):
    """An epoch between two evaluations with a step run missing."""
    i = next(i for i, r in enumerate(runs) if r[0] == EVAL) + 2
    del runs[i]


def _another_epochs_evaluation(spans, runs, edges):
    """The last evaluation in the trace is not the edge's."""
    edges[1]["epochs"] -= 1
    edges[1]["steps"] -= STEPS


def _no_evaluation(spans, runs, edges):
    runs[:] = [r for r in runs if r[0] != EVAL]


def _ring_lost_the_epoch(spans, runs, edges):
    spans[:] = [s for s in spans
                if s["attrs"]["epoch"] != edges[1]["epochs"] - 2]


def _edge_long_after(spans, runs, edges):
    edges[1]["t"] += 0.050


@pytest.mark.parametrize("damage, reason", [
    (_late_fetch, "contradict"),
    (_early_run, "contradict"),
    (_lost_step, "3 step runs before its evaluation, not 4"),
    # every epoch of the timeline is like the next, so the orderings hold
    # one epoch off: the last edge is what gives it away
    (_another_epochs_evaluation, "before the last edge"),
    (_no_evaluation, "no run of jit_eval_step"),
    (_ring_lost_the_epoch, "holds epoch"),
    (_edge_long_after, "before the last edge"),
])
def test_a_join_that_fails_a_check_gives_nothing_and_says_why(damage,
                                                              reason):
    spans, runs, edges = _timeline()
    edges = tuple(dict(e) for e in edges)
    damage(spans, runs, edges)
    said = []
    assert _join(spans, runs, edges, log=said.append) is None
    assert len(said) == 1 and reason in said[0], said


# -- the readers on spans made by hand ----------------------------------------

def test_epoch_end_and_input_read_the_windows_epochs(recorder):
    spans, runs, edges = _timeline()
    for s in spans:
        recorder.record(s)
    run = _run((spans, [], edges))
    # from the end of epoch_sync: the evaluation (2 ms of input, the
    # launch, 10 ms on the device, the fetch), the report (3 ms), the next
    # epoch's first input (4 ms)
    expected = 2.0 + 1e3 * LAUNCH + 10.0 + 1e3 * FETCH + 3.0 + 4.0
    reader = _reader("trainer.epoch_end_host_ms")
    assert reader.read(run) == pytest.approx(expected, abs=1e-6)
    assert _reader("trainer.input_ms").read(run) == pytest.approx(4.0)
    # read before the epoch after the last edge's has begun: one end fewer
    recorder.clear()
    for s in spans:
        if s["attrs"]["epoch"] < edges[1]["epochs"]:
            recorder.record(s)
    assert reader.read(run) == pytest.approx(expected, abs=1e-6)


@pytest.mark.parametrize("name", ["trainer.epoch_end_host_ms",
                                  "trainer.input_ms",
                                  "device.idle_named_share"])
def test_a_span_reader_with_no_spans_reads_nothing(name, recorder):
    """What a commit from before the spans gives: an empty recorder, or
    spans with no monotonic start and no epoch."""
    run = _run(_timeline())
    assert _reader(name).read(run) is None
    recorder.record({"name": "trainer.step", "ts": 1.0, "dur": 0.001,
                     "attrs": {"mode": "sync", "step": 3}})
    assert _reader(name).read(run) is None


@pytest.mark.parametrize("name", ["trainer.epoch_end_host_ms",
                                  "trainer.input_ms"])
def test_a_window_whose_first_epoch_left_the_ring_reads_nothing(name,
                                                                recorder):
    spans, _runs, edges = _timeline()
    for s in spans:
        if s["attrs"]["epoch"] > edges[0]["epochs"]:
            recorder.record(s)
    assert _reader(name).read(_run((spans, [], edges))) is None


def test_idle_is_shared_out_exactly():
    reader = _reader("device.idle_named_share")
    spans = [
        {"name": "trainer.epoch", "mono": 10.0, "dur": 1.0},
        {"name": "trainer.eval", "mono": 10.1, "dur": 0.2},
        {"name": "trainer.epoch_report", "mono": 10.3, "dur": 0.1},
        {"name": "trainer.input", "mono": 10.5, "dur": 0.1},
        {"name": "trainer.epoch", "mono": 11.25, "dur": 1.0},
    ]
    table = reader.idle_by_phase([(10.2, 10.45), (10.9, 11.5)], spans)
    assert table == pytest.approx({
        "trainer.eval": 0.1, "trainer.epoch_report": 0.1,
        reader.SELF: 0.05 + 0.1 + 0.25, reader.OUTSIDE: 0.25})
    assert sum(table.values()) == pytest.approx(0.25 + 0.6)


def test_idle_named_share_joins_the_clocks_and_prints_the_table(recorder,
                                                                capsys):
    spans, runs, edges = _timeline()
    for s in spans:
        recorder.record(s)
    share = _reader("device.idle_named_share").read(_run((spans, runs,
                                                          edges)))
    # every gap on this device lies in an epoch end, inside named spans
    assert 0.99 < share <= 1.0
    out = capsys.readouterr().out
    assert "[bench] clock join: host = device +" in out
    before_eval, before_step = (
        line for line in out.splitlines() if "idle on device 0" in line)
    assert "before:jit_eval_step: 3 gaps" in before_eval
    assert "trainer.epoch_sync" in before_eval
    assert "trainer.eval" in before_eval
    assert "before:jit_worker_step: 3 gaps" in before_step
    assert "trainer.input" in before_step
    assert "trainer.epoch_report" in before_step


def _mesh_trace(ops):
    modules = [("jit_step(1)", 0.0, 100e6), ("jit_step(1)", 100e6, 200e6)]
    return xplane.reduce_planes([xplane.Plane(
        "/device:TPU:0", {"XLA Ops": ops, "XLA Modules": modules})])


def test_exposed_collectives_count_once_where_they_overlap():
    ops = [("%fusion.1 = f32[] fusion()", 0.0, 80e6),
           ("%all-reduce.1 = f32[] all-reduce()", 80e6, 90e6),
           ("%all-reduce.2 = f32[] all-reduce()", 85e6, 96e6),
           ("%fusion.1 = f32[] fusion()", 100e6, 180e6),
           ("%all-gather-start.3 = f32[] all-gather-start()", 180e6, 184e6),
           # outside the window of whole steps: not counted
           ("%all-reduce.1 = f32[] all-reduce()", 200e6, 260e6)]
    run = _run(([], [], ({}, {})), chips=4)
    run.trace = _mesh_trace(ops)
    assert _reader("mesh.exposed_ms").read(run) == pytest.approx(
        (16.0 + 4.0) / 2)


def test_exposed_is_nothing_without_a_collective_or_a_trace():
    run = _run(([], [], ({}, {})), chips=4)
    assert _reader("mesh.exposed_ms").read(run) is None
    run.trace = _mesh_trace([("%fusion.1 = f32[] fusion()", 0.0, 200e6)])
    assert _reader("mesh.exposed_ms").read(run) is None


# -- the committed benchmark with the new entries -----------------------------

def test_the_benchmark_with_the_four_chip_cell_meets_the_contract():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert validate.check_benchmark(bench) == []
    cell = spec.load_cell("vit-b16-sync-4chip")
    assert cell.chips == 4 and cell.traffic["driver"] == "sync_mesh"
    assert callable(cell.driver().start)
    one_chip = spec.load_cell("vit-b16-sync-1chip")
    assert cell.config == one_chip.config
    assert {k: v for k, v in cell.traffic.items() if k != "why"} == {
        k: v for k, v in one_chip.traffic.items() if k != "why"}
    assert set(cell.per_layer) == set(one_chip.per_layer) | {
        "mesh.exposed_ms"}
    for name in ("trainer.epoch_end_host_ms", "trainer.input_ms",
                 "device.idle_named_share", "mesh.exposed_ms"):
        assert name in cell.per_layer
        assert callable(spec.load_module("layer_metrics", name,
                                         cell.bench_dir).read)
    assert cell.end_to_end == one_chip.end_to_end


def test_the_span_readers_read_a_real_trainers_recorder(recorder):
    """Two epochs and a third of ``SyncTrainer`` in this process, on the CPU
    backend, with edges shaped as ``sync_mesh.Session.edge`` shapes them."""
    from distributed_parameter_server_for_ml_training_tpu.data import (
        synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.train \
        .distributed import DistributedConfig, SyncTrainer
    steps, batch, workers = 2, 8, 2
    trainer = SyncTrainer(
        synthetic_cifar100(n_train=steps * batch * workers, n_test=16,
                           num_classes=10, seed=3),
        DistributedConfig(mode="sync", num_workers=workers, num_epochs=3,
                          batch_size=batch, dtype="float32", num_classes=10,
                          model="vit_tiny", seed=3))
    trainer.train()

    def edge(epochs):
        done = [s for s in recorder.tail()
                if s["name"] == "trainer.eval"][epochs - 1]
        return {"t": hostspans.end(done), "epochs": epochs,
                "steps": epochs * steps, "attempted": epochs * steps,
                "images": epochs * steps * batch * workers,
                "steps_counted": epochs * steps, "dispatch_sum_s": 0.0,
                "dispatch_n": epochs * steps}

    run = _run(([], [], (edge(1), edge(3))))
    end_ms = _reader("trainer.epoch_end_host_ms").read(run)
    input_ms = _reader("trainer.input_ms").read(run)
    assert end_ms > 0 and input_ms > 0
    epochs = hostspans.by_epoch(hostspans.recorded())
    assert hostspans.window_epochs(run, epochs) == [1, 2]
    # an epoch end holds at least the next epoch's first input
    assert end_ms >= 1e3 * min(
        epochs[e]["trainer.input"][0]["dur"] for e in (1, 2))
    assert _reader("device.idle_named_share").read(run) is None  # no trace
