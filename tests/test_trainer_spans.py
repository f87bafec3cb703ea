"""The sync trainer's phase spans (telemetry/trace.py, ``always=True``): two
tiny epochs of ``SyncTrainer`` on the CPU backend leave one ``trainer.epoch``
root an epoch whose children are the phases in the order the loop runs them,
on the monotonic clock, whether tracing is on or not."""

import time

import pytest

from distributed_parameter_server_for_ml_training_tpu.data import (
    synthetic_cifar100)
from distributed_parameter_server_for_ml_training_tpu.telemetry import (
    get_recorder, get_registry, trace as trace_mod, trace_enabled,
    trace_span)
from distributed_parameter_server_for_ml_training_tpu.train.distributed \
    import DistributedConfig, SyncTrainer

STEPS, BATCH, WORKERS, EPOCHS = 2, 8, 2, 2
#: one epoch's children, in the order the loop runs them
ORDER = (["trainer.input", "trainer.step"] * STEPS
         + ["trainer.epoch_sync", "trainer.eval", "trainer.epoch_report",
            "trainer.checkpoint"])


def _trainer():
    return SyncTrainer(
        synthetic_cifar100(n_train=STEPS * BATCH * WORKERS, n_test=16,
                           num_classes=10, seed=5),
        DistributedConfig(mode="sync", num_workers=WORKERS,
                          num_epochs=EPOCHS, batch_size=BATCH,
                          dtype="float32", num_classes=10, model="vit_tiny",
                          seed=5))


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """Spans of one two-epoch run with a checkpoint directory, tracing off,
    and the goodput seconds it charged."""
    assert not trace_enabled()
    rec = get_recorder()
    rec.clear()
    compute = get_registry().counter("dps_goodput_seconds_total",
                                     category="compute")
    before, t0 = compute.value, time.monotonic()
    _trainer().train(checkpoint_dir=str(tmp_path_factory.mktemp("ckpt")))
    out = {"spans": rec.tail(), "t0": t0, "t1": time.monotonic(),
           "compute_s": compute.value - before}
    rec.clear()
    return out


def _epochs(spans):
    roots = [s for s in spans if s["name"] == "trainer.epoch"]
    return [(r, sorted((s for s in spans if s["parent_id"] == r["span_id"]),
                       key=lambda s: s["mono"])) for r in roots]


def test_every_epoch_is_one_root_with_its_phases_in_order(trained):
    epochs = _epochs(trained["spans"])
    assert [r["attrs"]["epoch"] for r, _ in epochs] == list(range(EPOCHS))
    assert {s["name"] for s in trained["spans"]} == set(ORDER) | {
        "trainer.epoch"}
    for root, children in epochs:
        assert root["parent_id"] is None
        assert root["attrs"]["first_step"] == root["attrs"]["epoch"] * STEPS
        assert [c["name"] for c in children] == ORDER
        assert {c["trace_id"] for c in children} == {root["trace_id"]}
    # nothing else was recorded: no span without an epoch for a parent
    assert len(trained["spans"]) == EPOCHS * (len(ORDER) + 1)


def test_phases_carry_their_epoch_and_step(trained):
    for root, children in _epochs(trained["spans"]):
        epoch = root["attrs"]["epoch"]
        assert all(c["attrs"]["epoch"] == epoch for c in children)
        for name in ("trainer.input", "trainer.step"):
            steps = [c["attrs"]["step"] for c in children
                     if c["name"] == name]
            assert steps == [epoch * STEPS + i for i in range(STEPS)]
        by_name = {c["name"]: c for c in children}
        # a uint8 image batch and its int32 labels
        assert by_name["trainer.input"]["attrs"]["bytes"] == \
            BATCH * WORKERS * (32 * 32 * 3 + 4)
        assert by_name["trainer.step"]["attrs"]["mode"] == "sync"
        assert by_name["trainer.eval"]["attrs"]["batches"] == 1
        sync = by_name["trainer.epoch_sync"]
        assert sync["mono"] <= sync["attrs"]["ready_mono"] \
            <= sync["mono"] + sync["dur"]


def test_phases_nest_in_their_epoch_without_overlapping(trained):
    for root, children in _epochs(trained["spans"]):
        at = root["mono"]
        for c in children:
            assert c["mono"] >= at, (c["name"], "overlaps the one before")
            at = c["mono"] + c["dur"]
        assert at <= root["mono"] + root["dur"]
        named = sum(c["dur"] for c in children)
        assert 0 < named <= root["dur"]
        # the loop's own bookkeeping is all that is left unnamed
        assert root["dur"] - named < 0.25 * root["dur"]


def test_spans_start_on_the_monotonic_clock(trained):
    for s in trained["spans"]:
        assert trained["t0"] <= s["mono"] <= s["mono"] + s["dur"] \
            <= trained["t1"]
        assert abs((s["ts"] - time.time()) - (s["mono"] - time.monotonic())) \
            < 1.0   # the wall start is kept beside it


def test_the_epoch_ends_wait_is_charged_to_compute(trained):
    """The goodput ledger files the step calls, the wait for the device and
    the evaluation under compute: at least what those spans lasted."""
    waited = sum(s["dur"] for s in trained["spans"] if s["name"] in (
        "trainer.step", "trainer.epoch_sync", "trainer.eval"))
    assert trained["compute_s"] >= 0.95 * waited > 0


def test_spans_are_recorded_with_tracing_off_and_other_sites_stay_silent(
        trained):
    assert not trace_enabled()
    rec = get_recorder()
    rec.clear()
    with trace_span("worker.step", root=True, step=1) as sp:
        sp.attrs["x"] = 1                   # a PS span site: the no-op
    with trace_span("store.push", backend="python"):
        pass
    assert len(rec) == 0
    with trace_span("trainer.epoch", root=True, always=True, epoch=0):
        with trace_span("store.fetch"):     # nested under an always span
            pass
    assert [s["name"] for s in rec.tail()] == ["trainer.epoch"]
    rec.clear()


def test_the_ring_keeps_its_bound_and_says_what_it_dropped(monkeypatch):
    ring = trace_mod.FlightRecorder(maxlen=8, role="test")
    monkeypatch.setattr(trace_mod, "_RECORDER", ring)
    _trainer().train()
    total = EPOCHS * len(ORDER)   # no checkpoint directory: one span fewer
    assert len(ring) == 8
    payload = ring.dump_payload("test")
    assert payload["dropped_spans"] == total - 8
    assert payload["buffer_size"] == 8 and payload["span_count"] == 8
    # the newest spans are the ones kept: the run's last is its last epoch
    assert payload["spans"][-1]["name"] == "trainer.epoch"
    assert payload["spans"][-1]["attrs"]["epoch"] == EPOCHS - 1


def test_span_ids_do_not_follow_the_programs_random_seed():
    """Ids are drawn without a system call, from a generator of the
    module's own: a program that seeds ``random`` (two processes with one
    seed) still gets ids of its own."""
    import random
    rec = get_recorder()
    rec.clear()
    for _ in range(2):
        random.seed(0)
        with trace_span("trainer.epoch", root=True, always=True, epoch=0):
            pass
    first, second = rec.tail()
    assert first["span_id"] != second["span_id"]
    assert first["trace_id"] != second["trace_id"]
    assert all(len(s[k]) == 16 and int(s[k], 16) >= 0
               for s in (first, second) for k in ("span_id", "trace_id"))
    rec.clear()
