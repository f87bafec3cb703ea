"""The parameter-server path's always-on record: with no ``--trace`` and no
``enable_tracing()`` a worker run leaves its phase spans, the two
device-complete stamps (``worker.epoch_sync``, ``store.sync``) and the
store's push / apply / fetch spans in the flight recorder, on the monotonic
clock, and no span site synchronizes the device on the record's account.
Also: a worker's second evaluation compiles nothing, and the record's
reader (``analysis/traces.py:ps_phase_report``, ``cli perf phases``).
"""

import contextlib
import json
import time

import jax
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu import cli
from distributed_parameter_server_for_ml_training_tpu.analysis import (
    load_trace_dumps, ps_phase_report, render_ps_phase_table)
from distributed_parameter_server_for_ml_training_tpu.data import (
    synthetic_cifar100)
from distributed_parameter_server_for_ml_training_tpu.ps import (
    DeviceParameterStore, ParameterStore, StoreConfig, WorkerConfig,
    run_workers)
from distributed_parameter_server_for_ml_training_tpu.ps import worker as W
from distributed_parameter_server_for_ml_training_tpu.telemetry import (
    SPAN_CATALOG, disable_tracing, enable_tracing, get_recorder,
    start_metrics_server, trace_enabled)
from distributed_parameter_server_for_ml_training_tpu.utils import (
    flatten_params)

BATCH = 32
STEP_CHILDREN = ("worker.fetch_wait", "worker.compute", "worker.push_wait")


@pytest.fixture(scope="module")
def model():
    from distributed_parameter_server_for_ml_training_tpu.models import ResNet
    return ResNet(stage_sizes=(1, 1), num_filters=8, num_classes=10)


@pytest.fixture(scope="module")
def dataset():
    return synthetic_cifar100(n_train=640, n_test=64, num_classes=10, seed=3)


def _flat(model):
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 32, 32, 3), np.float32), train=False)
    return flatten_params(variables["params"])


@contextlib.contextmanager
def counted_blocks():
    """The worker module's ``jax`` with its ``block_until_ready`` counted:
    yields a one-element list holding the number of calls."""
    calls = [0]
    real_jax = W.jax

    class _Jax:
        @staticmethod
        def block_until_ready(tree):
            calls[0] += 1
            return real_jax.block_until_ready(tree)

        def __getattr__(self, name):
            return getattr(real_jax, name)

    W.jax = _Jax()
    try:
        yield calls
    finally:
        W.jax = real_jax


@pytest.fixture(scope="module")
def ran(model, dataset):
    """Two workers, async, the device store, tracing off."""
    assert not trace_enabled()
    rec = get_recorder()
    rec.clear()
    store = DeviceParameterStore(_flat(model), StoreConfig(
        mode="async", total_workers=2, learning_rate=0.05,
        staleness_bound=5))
    store.wait_every = 4
    t0 = time.monotonic()
    with counted_blocks() as blocked:
        results = run_workers(
            store, model, dataset, n_workers=2,
            config=WorkerConfig(batch_size=BATCH, num_epochs=2,
                                augment=False))
    out = {"spans": rec.tail(), "store": store, "results": results,
           "t0": t0, "t1": time.monotonic(), "blocked": blocked[0]}
    rec.clear()
    return out


def _named(ran, name):
    return [s for s in ran["spans"] if s["name"] == name]


def test_both_new_names_are_in_the_catalog():
    assert "worker.epoch_sync" in SPAN_CATALOG and "store.sync" in SPAN_CATALOG


def test_a_run_without_tracing_leaves_every_phase_span(ran):
    assert not trace_enabled()
    steps = [s for s in _named(ran, "worker.step")
             if not s["attrs"].get("epoch_open")]
    done = sum(r.local_steps_completed for r in ran["results"])
    assert len(steps) == done == 2 * 2 * (320 // BATCH)
    by_parent = {}
    for s in ran["spans"]:
        by_parent.setdefault(s["parent_id"], []).append(s)
    for step in steps:
        children = {c["name"] for c in by_parent[step["span_id"]]}
        assert set(STEP_CHILDREN[1:]) <= children, children
        assert {"worker", "epoch", "step"} <= set(step["attrs"])
        for c in by_parent[step["span_id"]]:
            if c["name"] in STEP_CHILDREN:
                assert c["attrs"]["worker"] == step["attrs"]["worker"]
                assert c["attrs"]["step"] == step["attrs"]["step"]
                assert c["tid"] == step["tid"]
    # an epoch's first fetch has a root of its own (epoch_open); every
    # later step fetches inside its own
    assert len(_named(ran, "worker.fetch_wait")) == done
    assert len(_named(ran, "worker.epoch_sync")) == 4
    assert len(_named(ran, "worker.eval")) == 4
    # the device store runs no codec: no worker.codec span
    assert not _named(ran, "worker.codec")


def test_every_span_starts_on_the_monotonic_clock(ran):
    for s in ran["spans"]:
        assert ran["t0"] <= s["mono"] <= s["mono"] + s["dur"] <= ran["t1"]
        assert isinstance(s["tid"], int)


def test_a_worker_has_its_own_thread_and_its_spans_do_not_overlap(ran):
    steps = _named(ran, "worker.step")
    tids = {s["attrs"]["worker"]: s["tid"] for s in steps}
    assert len(set(tids.values())) == 2
    for worker, tid in tids.items():
        mine = sorted((s for s in steps if s["tid"] == tid),
                      key=lambda s: s["mono"])
        assert all(s["attrs"]["worker"] == worker for s in mine)
        for a, b in zip(mine, mine[1:]):
            assert a["mono"] + a["dur"] <= b["mono"]


def test_no_span_site_blocked_on_the_device(ran):
    """Under ``always`` alone ``worker.compute`` must not wait for its
    gradients: the wait belongs to ``enable_tracing()``."""
    assert ran["blocked"] == 0


def test_under_enable_tracing_compute_does_block(model, dataset):
    store = ParameterStore(_flat(model), StoreConfig(
        mode="async", total_workers=1, learning_rate=0.05))
    size = get_recorder().maxlen
    enable_tracing()
    try:
        with counted_blocks() as blocked:
            run_workers(store, model, dataset, n_workers=1,
                        config=WorkerConfig(batch_size=64, num_epochs=1,
                                            augment=False,
                                            eval_each_epoch=False))
    finally:
        disable_tracing()
    assert blocked[0] == 640 // 64
    assert get_recorder().maxlen == size
    # the host store does run a codec stage: its spans are there
    assert any(s["name"] == "worker.codec" for s in get_recorder().tail())
    get_recorder().clear()


def test_store_sync_stamps_what_is_complete_on_the_device(ran):
    syncs = sorted(_named(ran, "store.sync"),
                   key=lambda s: s["attrs"]["ready_mono"])
    store = ran["store"]
    assert len(syncs) == store.global_step // store.wait_every
    updates = [s["attrs"]["updates"] for s in syncs]
    assert updates == sorted(updates) and len(set(updates)) == len(updates)
    assert updates[-1] <= store.global_step
    for s in syncs:
        a = s["attrs"]
        assert s["mono"] <= a["ready_mono"] <= s["mono"] + s["dur"] + 1e-3
        assert a["rejected"] >= 0
        # images applied at the stamp: the accepted pushes that ended
        # before the sync began are all among its updates
        ended = sum(1 for p in _named(ran, "store.push")
                    if p["attrs"]["accepted"]
                    and p["mono"] + p["dur"] <= s["mono"])
        assert ended <= a["updates"] <= ended + 2   # two workers in flight
    assert store.stats.gradients_rejected >= syncs[-1]["attrs"]["rejected"]


def test_store_push_says_who_pushed_and_apply_how_stale(ran):
    pushes = _named(ran, "store.push")
    assert len(pushes) == sum(r.pushes_accepted + r.pushes_rejected
                              for r in ran["results"])
    for p in pushes:
        a = p["attrs"]
        assert set(a) == {"backend", "worker", "accepted"}
        assert a["worker"] in (0, 1) and a["backend"] == "device"
        assert isinstance(a["accepted"], bool)
    # an apply if and only if the push was accepted, inside it, with the
    # staleness the push was judged by: the guarantee's record
    applies = {a["parent_id"]: a for a in _named(ran, "store.apply")}
    assert len(applies) == len(_named(ran, "store.apply"))
    assert set(applies) == {p["span_id"] for p in pushes
                            if p["attrs"]["accepted"]}
    for a in applies.values():
        assert 0 <= a["attrs"]["staleness"] <= 5
        assert set(a["attrs"]) == {"backend", "mode", "staleness", "weight"}
    assert len(_named(ran, "store.fetch")) >= len(pushes)


def test_a_workers_epoch_sync_carries_what_the_issue_asked_for(ran):
    for result in ran["results"]:
        mine = sorted((s for s in _named(ran, "worker.epoch_sync")
                       if s["attrs"]["worker"] == result.worker_id),
                      key=lambda s: s["attrs"]["epoch"])
        assert [s["attrs"]["epoch"] for s in mine] == [0, 1]
        last = mine[-1]["attrs"]
        assert set(last) == {"worker", "epoch", "steps", "ready_mono"}
        assert last["steps"] == result.local_steps_completed
        assert mine[-1]["mono"] <= last["ready_mono"] \
            <= mine[-1]["mono"] + mine[-1]["dur"] + 1e-3
        for e in _named(ran, "worker.eval"):
            assert set(e["attrs"]) == {"worker", "epoch"}


def test_the_comms_threads_codec_spans_carry_the_submitting_step(
        model, dataset):
    """``--overlap``: push and prefetch run on the comms thread while the
    training thread is a step ahead; a ``worker.codec`` span there says
    the step that submitted the item, not the one the training thread is
    in by then."""
    assert not trace_enabled()
    rec = get_recorder()
    rec.clear()
    store = ParameterStore(_flat(model), StoreConfig(
        mode="async", total_workers=1, learning_rate=0.05))
    run_workers(store, model, dataset, n_workers=1,
                config=WorkerConfig(batch_size=64, num_epochs=1,
                                    augment=False, overlap=True,
                                    eval_each_epoch=False))
    spans = rec.tail()
    rec.clear()
    waits = {s["attrs"]["step"]: s for s in spans
             if s["name"] == "worker.push_wait"}
    assert sorted(waits) == list(range(640 // 64))
    training_tid = next(iter(waits.values()))["tid"]
    codecs = [s for s in spans if s["name"] == "worker.codec"
              and s["tid"] != training_tid]
    for stage in ("encode", "decode"):
        mine = [c for c in codecs if c["attrs"]["stage"] == stage]
        # one a pushed step, each step once
        assert sorted(c["attrs"]["step"] for c in mine) == sorted(waits)
    for c in codecs:
        assert c["attrs"]["worker"] == 0 and c["attrs"]["epoch"] == 0
        # the item's work starts after the step that submitted it began
        # to push; under the next step's label it would start before
        assert c["mono"] >= waits[c["attrs"]["step"]]["mono"]
        # with tracing off pipeline.comms is no span: a root of its own
        assert c["parent_id"] is None
    assert not any(s["name"] == "pipeline.comms" for s in spans)


def test_evaluate_twice_compiles_once(model, dataset):
    """``tx`` is a static field of ``eval_step``'s argument: built once a
    process, the second evaluation finds the first one's program."""
    eval_step = jax.jit(W.make_eval_step())
    worker = W.PSWorker(
        ParameterStore(_flat(model), StoreConfig(mode="async",
                                                 total_workers=1)),
        model, dataset, WorkerConfig(batch_size=BATCH, eval_batch_size=64),
        eval_step=eval_step)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 32, 32, 3), np.float32), train=False)
    compiles = []      # jax.monitoring has no unregister: it stays, idle

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    first = worker.evaluate(variables["params"], variables["batch_stats"])
    n_first = len(compiles)
    assert n_first >= 1 and eval_step._cache_size() == 1
    second = worker.evaluate(variables["params"], variables["batch_stats"])
    assert first == second
    assert eval_step._cache_size() == 1
    assert len(compiles) == n_first
    # and another worker of the process shares it
    other = W.PSWorker(worker.store, model, dataset, worker.config,
                       eval_step=eval_step)
    other.evaluate(variables["params"], variables["batch_stats"])
    assert eval_step._cache_size() == 1


# -- the record's reader -------------------------------------------------------

def _span(name, mono, dur, **attrs):
    return {"name": name, "ts": 1000.0 + mono, "mono": mono, "dur": dur,
            "span_id": f"{name}-{mono}", "parent_id": None, "attrs": attrs}


def _synthetic():
    """Two workers; worker 0: one opening fetch and two steps, an epoch
    end; the store: three pushes (one refused), two syncs 2 s and 16
    updates apart."""
    w0 = dict(worker=0, epoch=0)
    return [
        _span("worker.step", 0.0, 0.1, step=0, epoch_open=True, **w0),
        _span("worker.fetch_wait", 0.0, 0.1, step=0, **w0),
        _span("worker.step", 0.2, 1.0, step=0, **w0),
        _span("worker.compute", 0.2, 0.3, step=0, **w0),
        _span("worker.push_wait", 0.5, 0.7, step=0, **w0),
        _span("worker.codec", 0.5, 0.05, step=0, stage="encode", **w0),
        _span("worker.step", 1.2, 0.5, step=1, **w0),
        _span("worker.fetch_wait", 1.2, 0.2, step=1, **w0),
        _span("worker.compute", 1.4, 0.3, step=1, **w0),
        _span("worker.epoch_sync", 1.8, 0.4, steps=2, ready_mono=2.2, **w0),
        _span("worker.eval", 2.2, 0.25, **w0),
        _span("worker.step", 0.0, 3.0, worker=1, epoch=0, step=0),
        _span("store.push", 0.5, 0.7, worker=0, accepted=True),
        _span("store.apply", 0.5, 0.1, mode="async", staleness=1, weight=0.5),
        _span("store.push", 0.6, 0.0, worker=1, accepted=False),
        _span("store.push", 0.7, 0.1, worker=1, accepted=True),
        _span("store.apply", 0.7, 0.05, mode="async", staleness=4,
              weight=0.2),
        _span("store.fetch", 0.0, 0.02),
        _span("store.sync", 0.6, 0.5, updates=8, rejected=0, ready_mono=1.1),
        _span("store.sync", 2.9, 0.2, updates=24, rejected=1,
              ready_mono=3.1),
    ]


def test_the_phase_report_adds_up_a_synthetic_record():
    rep = ps_phase_report(_synthetic())
    w0 = rep["workers"][0]
    assert (w0["steps"], w0["epochs"]) == (2, 1)
    assert w0["observed_s"] == pytest.approx(2.45)
    assert w0["step_s"] == pytest.approx(1.6)
    assert w0["phases_s"] == pytest.approx({
        "fetch_wait": 0.3, "compute": 0.6, "push_wait": 0.7, "codec": 0.05,
        "epoch_sync": 0.4, "eval": 0.25})
    assert w0["unnamed_s"] == pytest.approx(2.45 - 1.6 - 0.4 - 0.25)
    assert rep["workers"][1]["steps"] == 1
    st = rep["store"]
    assert (st["pushes"], st["rejected"]) == (3, 1)
    assert st["reject_share"] == pytest.approx(1 / 3, abs=1e-6)
    assert (st["applies"], st["staleness_mean"], st["staleness_max"]) \
        == (2, 2.5, 4)
    assert st["apply_s"] == pytest.approx(0.15)
    assert st["fetch_s"] == pytest.approx(0.02)
    assert (st["syncs"], st["sync_wait_s"]) == (2, pytest.approx(0.7))
    assert st["updates"] == [8, 24]
    assert st["updates_per_s"] == pytest.approx(16 / 2.0)
    # two workers: a stamp's surplus is at most one gradient step of 16
    assert st["rate_uncertainty"] == pytest.approx(1 / 16)
    text = render_ps_phase_table(rep)
    assert "8 -> 24" in text and "+-6.25%" in text and "1 refused" in text


@pytest.mark.parametrize("spans", [[], _synthetic()[-1:],
                                   [_span("trainer.step", 0.0, 1.0)]],
                         ids=["empty", "one-sync", "sync-trainer"])
def test_the_phase_report_of_a_record_without_the_path_says_nothing(spans):
    rep = ps_phase_report(spans)
    assert rep["workers"] == {}
    st = rep["store"]
    assert st["updates_per_s"] is None and st["rate_uncertainty"] is None
    assert st["reject_share"] is None and st["staleness_mean"] is None
    assert "no worker.* span" in render_ps_phase_table(rep)


def test_the_phase_report_of_a_real_run(ran):
    rep = ps_phase_report(ran["spans"])
    assert sorted(rep["workers"]) == [0, 1]
    for result in ran["results"]:
        w = rep["workers"][result.worker_id]
        assert w["steps"] == result.local_steps_completed
        assert w["epochs"] == 2
        assert 0 < w["step_s"] <= w["observed_s"]
        inside = sum(w["phases_s"][p]
                     for p in ("fetch_wait", "compute", "push_wait"))
        assert 0 < inside <= w["step_s"]
        assert w["phases_s"]["codec"] == 0.0    # the device store has none
    st, store = rep["store"], ran["store"]
    assert st["pushes"] == sum(r.pushes_accepted + r.pushes_rejected
                               for r in ran["results"])
    assert st["applies"] == store.global_step
    assert 0 <= st["staleness_mean"] <= st["staleness_max"] <= 5
    assert st["syncs"] == store.global_step // store.wait_every
    assert st["updates"][0] < st["updates"][1] <= store.global_step
    assert st["rate_uncertainty"] == pytest.approx(
        1 / (st["updates"][1] - st["updates"][0]), abs=1e-6)
    # the waits for the device are time inside the workers' push waits
    assert st["sync_wait_s"] <= sum(w["phases_s"]["push_wait"]
                                    for w in rep["workers"].values())


def test_cli_perf_phases_reads_dumps_and_a_live_endpoint(ran, tmp_path,
                                                         capsys):
    half = len(ran["spans"]) // 2
    (tmp_path / "trace-a-1-atexit.json").write_text(
        json.dumps({"spans": ran["spans"][:half + 10]}))
    (tmp_path / "late.json").write_text(json.dumps(ran["spans"][half:]))
    sources = [str(tmp_path), str(tmp_path / "late.json")]
    # two overlapping dumps are one record
    assert len(load_trace_dumps([str(tmp_path / "trace-a-1-atexit.json"),
                                 sources[1]])) == len(ran["spans"])
    assert cli.main(["perf", "phases", *sources, "--json"]) == 0
    assert json.loads(capsys.readouterr().out) \
        == json.loads(json.dumps(ps_phase_report(ran["spans"])))
    # a live process that never enabled tracing, through /debug/trace
    rec = get_recorder()
    rec.clear()
    for s in ran["spans"][-200:]:
        rec.record(s)
    server, port = start_metrics_server(port=0)
    try:
        assert cli.main(["perf", "phases",
                         f"http://127.0.0.1:{port}/debug/trace"]) == 0
    finally:
        server.shutdown()
        rec.clear()
    out = capsys.readouterr().out
    assert out.startswith("worker  steps") and "store.sync" in out
    # nothing of the path in the record: exit code 1, and it says so
    (tmp_path / "none.json").write_text("[]")
    assert cli.main(["perf", "phases", str(tmp_path / "none.json")]) == 1
    assert "no worker.* span" in capsys.readouterr().out
