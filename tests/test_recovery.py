"""Crash-recovery subsystem tests (docs/ROBUSTNESS.md, tier-1).

Covers the four legs of the crash-tolerance story:

- durable server state: versioned snapshot/restore round-trip including
  the push-token journal;
- exactly-once across restarts: a push replayed against a RESTORED server
  dedupes from the journal instead of double-applying, and zombie tokens
  (count below last-seen) can neither re-apply nor evict newer records;
- worker session resume: a PSWorker rides through a server kill+restart
  (reconnect, re-register, refetch, reconcile) and finishes the run;
- fault injection: deterministic schedules (same seed -> same schedule),
  client plug exercising retry and lost-reply dedupe paths.
"""

import threading
import time

import grpc
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.checkpoint import (
    STORE_SNAPSHOT_VERSION, load_store_record, restore_server_state,
    save_store)
from distributed_parameter_server_for_ml_training_tpu.comms import (
    FaultInjector, RemoteStore, SessionLostError, encode_tensor_dict, serve)
from distributed_parameter_server_for_ml_training_tpu.comms.service import (
    DUP_WAIT_CAP_S, ParameterService, pack_msg, parse_push_token, unpack_msg)
from distributed_parameter_server_for_ml_training_tpu.ps import (
    ParameterStore, StoreConfig)


def _push_request(wid, token, value, fetched_step=0, n=4):
    return pack_msg(
        {"worker_id": wid, "fetched_step": fetched_step,
         "push_token": token},
        encode_tensor_dict({"w": np.full(n, value, np.float32)}))


class TestPushTokenOrdering:
    """Round-5 ADVICE (medium): the dedupe table must order a client's
    tokens by their counter, not just match the most recent one."""

    def test_parse_push_token(self):
        assert parse_push_token("abc123:7") == ("abc123", 7)
        assert parse_push_token("n:0") == ("n", 0)
        # no parsable counter -> exact-match degradation
        assert parse_push_token("oldstyle") == ("oldstyle", -1)
        assert parse_push_token("weird:x") == ("weird:x", -1)

    def test_zombie_token_never_reapplies_nor_evicts(self):
        """The double-apply scenario: push n:1 times out client-side but
        its ZOMBIE request arrives at the server AFTER the retry succeeded
        and n:2 already landed. The zombie must (a) not apply, (b) not
        evict n:2's record — so a genuine retry of n:2 still replays
        instead of re-applying."""
        store = ParameterStore({"w": np.ones(4, np.float32)}, StoreConfig(
            mode="sync", total_workers=1, push_codec="none"))
        store.register_worker()
        svc = ParameterService(store)

        r1 = _push_request(0, "n:1", 0.5)
        r2 = _push_request(0, "n:2", 0.25, fetched_step=1)
        m1, _ = unpack_msg(svc.push_gradrients(r1, None))
        m2, _ = unpack_msg(svc.push_gradrients(r2, None))
        assert m1["accepted"] and m2["accepted"]
        assert store.global_step == 2
        w_after = store.parameters["w"].copy()

        # Zombie n:1 arrives late: refused as a stale duplicate.
        mz, _ = unpack_msg(svc.push_gradrients(r1, None))
        assert mz.get("duplicate") is True
        assert mz.get("stale_token") is True
        assert store.global_step == 2
        np.testing.assert_array_equal(store.parameters["w"], w_after)

        # n:2's record survived the zombie: its retry REPLAYS (no apply).
        mr, _ = unpack_msg(svc.push_gradrients(r2, None))
        assert mr.get("duplicate") is True and mr["accepted"]
        assert not mr.get("stale_token")
        assert store.global_step == 2
        np.testing.assert_array_equal(store.parameters["w"], w_after)

    def test_duplicate_wait_bounded_by_caller_deadline(self):
        """Round-5 ADVICE (low): a duplicate's wait for the original's
        outcome must respect the CALLER's remaining deadline (and the cap
        DUP_WAIT_CAP_S), not a flat 120 s that outlives every client."""
        store = ParameterStore({"w": np.ones(4, np.float32)}, StoreConfig(
            mode="sync", total_workers=1, push_codec="none"))
        store.register_worker()
        svc = ParameterService(store)

        release = threading.Event()
        original_push = store.push

        def slow_push(wid, grads, fetched_step):
            release.wait(10.0)
            return original_push(wid, grads, fetched_step)

        store.push = slow_push
        req = _push_request(0, "slow:1", 0.5)
        t = threading.Thread(target=svc.push_gradrients, args=(req, None),
                             daemon=True)
        t.start()
        time.sleep(0.2)  # original is now parked in slow_push

        class Ctx:
            aborted = None

            def time_remaining(self):
                return 0.6  # caller deadline nearly out

            def abort(self, code, detail):
                self.aborted = (code, detail)
                raise grpc.RpcError(detail)

        ctx = Ctx()
        t0 = time.monotonic()
        with pytest.raises(grpc.RpcError):
            svc.push_gradrients(req, ctx)
        waited = time.monotonic() - t0
        # Bounded by remaining-deadline minus margin, nowhere near 120 s
        # (or even the 10 s the original is stuck for).
        assert waited < 2.0, waited
        assert ctx.aborted[0] == grpc.StatusCode.UNAVAILABLE
        release.set()
        t.join(timeout=10)
        assert DUP_WAIT_CAP_S <= 60.0  # cap stays under client rpc_timeout


class TestDurableServerState:
    def _svc(self, mode="sync", **kw):
        store = ParameterStore(
            {"w": np.ones(4, np.float32)},
            StoreConfig(mode=mode, total_workers=1, push_codec="none",
                        **kw))
        store.register_worker()
        return store, ParameterService(store)

    def test_snapshot_roundtrip_with_journal(self, tmp_path):
        """Current-format record: params + step + aggregation config +
        the push-token journal all survive the round trip (v3 adds the
        CRC stamp + migration block; tests/test_checkpoint.py pins
        those)."""
        store, svc = self._svc(mode="async", staleness_bound=7)
        svc.push_gradrients(_push_request(0, "j:1", 0.5), None)
        svc.push_gradrients(_push_request(0, "j:2", 0.25, fetched_step=1),
                            None)
        save_store(store, str(tmp_path), journal_fn=svc.journal_snapshot)

        params, meta = load_store_record(str(tmp_path))
        assert meta["format_version"] == STORE_SNAPSHOT_VERSION
        assert meta["global_step"] == 2
        assert meta["aggregation"]["mode"] == "async"
        assert meta["aggregation"]["staleness_bound"] == 7
        journal = meta["push_journal"]
        assert [(e["nonce"], e["count"]) for e in journal] == [("j", 2)]
        assert journal[0]["accepted"] is True
        np.testing.assert_array_equal(params["w"], store.parameters["w"])

    def test_journal_skips_inflight_pushes(self, tmp_path):
        """An in-flight push has no outcome yet; journaling a guess would
        make the restarted server lie to its retry."""
        store, svc = self._svc()
        hold = threading.Event()
        original = store.push

        def parked(wid, grads, fetched_step):
            hold.wait(10.0)
            return original(wid, grads, fetched_step)

        store.push = parked
        t = threading.Thread(
            target=svc.push_gradrients,
            args=(_push_request(0, "p:1", 0.5), None), daemon=True)
        t.start()
        time.sleep(0.2)
        assert svc.journal_snapshot() == []  # in flight -> not journaled
        hold.set()
        t.join(timeout=10)
        assert [e["nonce"] for e in svc.journal_snapshot()] == ["p"]

    def test_journal_captured_before_params_snapshot(self, tmp_path):
        """Consistency ordering: a push landing BETWEEN the journal
        capture and the params snapshot must be in the params but NOT the
        journal — a journaled 'accepted' absent from the restored params
        would replay success for a gradient the model lost (the silent-
        loss failure the journal exists to prevent)."""
        store, svc = self._svc(mode="async")
        svc.push_gradrients(_push_request(0, "o:1", 0.5), None)
        original = store.snapshot

        def racy_snapshot():
            svc.push_gradrients(_push_request(0, "o:2", 0.25, 1), None)
            return original()

        store.snapshot = racy_snapshot
        save_store(store, str(tmp_path), journal_fn=svc.journal_snapshot)
        _, meta = load_store_record(str(tmp_path))
        assert meta["global_step"] == 2  # o:2's apply IS in the params
        assert [(e["nonce"], e["count"])
                for e in meta["push_journal"]] == [("o", 1)]

    def test_push_replay_across_restart_no_double_apply(self, tmp_path):
        """THE crash-recovery crucible: the server applies a push, its
        reply is lost, the server dies; the client's retry reaches the
        RESTARTED server — which must replay the journaled outcome, not
        re-apply the gradient."""
        store1, svc1 = self._svc()
        req = _push_request(0, "r:1", 0.5)
        m1, _ = unpack_msg(svc1.push_gradrients(req, None))
        assert m1["accepted"] and store1.global_step == 1
        save_store(store1, str(tmp_path), journal_fn=svc1.journal_snapshot)
        # server process dies here; a new one restores
        store2 = ParameterStore(
            {"w": np.zeros(4, np.float32)},
            StoreConfig(mode="sync", total_workers=1, push_codec="none"))
        store2.register_worker()
        svc2 = ParameterService(store2)
        step, journal_n = restore_server_state(store2, svc2, str(tmp_path))
        assert (step, journal_n) == (1, 1)
        np.testing.assert_array_equal(store2.parameters["w"],
                                      store1.parameters["w"])

        # The retry (same bytes) replays; params and step do not move.
        m2, _ = unpack_msg(svc2.push_gradrients(req, None))
        assert m2.get("duplicate") is True and m2["accepted"]
        assert store2.global_step == 1
        np.testing.assert_array_equal(store2.parameters["w"],
                                      store1.parameters["w"])
        # A genuinely new push still applies.
        m3, _ = unpack_msg(
            svc2.push_gradrients(_push_request(0, "r:2", 0.25, 1), None))
        assert m3["accepted"] and not m3.get("duplicate")
        assert store2.global_step == 2

    def test_snapshot_meta_published_before_npz(self, tmp_path):
        """Atomicity ordering: every visible .npz has its .json beside it
        (restore discovers by npz — a crash between the two renames must
        never leave a metadata-less snapshot)."""
        store, svc = self._svc()
        save_store(store, str(tmp_path), journal_fn=svc.journal_snapshot)
        import os
        names = os.listdir(tmp_path)
        for f in names:
            if f.endswith(".npz"):
                assert f.replace(".npz", ".json") in names


class TestWorkerSessionResume:
    def _model_store(self, tiny_model, mode="sync", **kw):
        import jax

        from distributed_parameter_server_for_ml_training_tpu.utils.pytree \
            import flatten_params
        model = tiny_model()
        variables = model.init(jax.random.PRNGKey(0),
                               np.zeros((1, 32, 32, 3), np.float32),
                               train=False)
        flat = flatten_params(variables["params"])
        store = ParameterStore(
            {k: np.array(v) for k, v in flat.items()},
            StoreConfig(mode=mode, total_workers=1, elastic=True,
                        worker_timeout=60.0, push_codec="none", **kw))
        return model, flat, store

    @pytest.mark.parametrize("overlap", [False, True])
    def test_worker_reconnects_through_server_restart(self, tiny_model,
                                                      tmp_path, overlap):
        """Kill the server at a DETERMINISTIC point (just before the
        worker's 3rd push leaves), restore a fresh one from its snapshot
        on the SAME port: the worker's reconnect state machine
        re-registers, re-fetches at the restored step, reconciles its
        in-flight gradient (same-token repush), and the run completes
        with every gradient applied exactly once."""
        from distributed_parameter_server_for_ml_training_tpu.data import (
            synthetic_cifar100)
        from distributed_parameter_server_for_ml_training_tpu.ps import (
            PSWorker, WorkerConfig)

        model, flat, store1 = self._model_store(tiny_model)
        svc1 = ParameterService(store1)
        server1, port = serve(store1, port=0, service=svc1)

        client = RemoteStore(f"localhost:{port}", rpc_timeout=5.0,
                             rpc_retries=1, rpc_backoff=0.05)
        ds = synthetic_cifar100(n_train=96, n_test=16, num_classes=10)
        w = PSWorker(client, model, ds,
                     WorkerConfig(batch_size=16, num_epochs=3,
                                  sync_steps=2, overlap=overlap,
                                  augment=False, eval_each_epoch=False,
                                  reconnect_timeout=60.0,
                                  reconnect_backoff=0.05))

        killed = threading.Event()
        restarted = threading.Event()
        holder = {}

        def restart_after_kill():
            killed.wait(120)
            time.sleep(0.3)  # the worker's retries see UNAVAILABLE first
            store2 = ParameterStore(
                {k: np.zeros_like(v) for k, v in flat.items()},
                StoreConfig(mode="sync", total_workers=1, elastic=True,
                            worker_timeout=60.0, push_codec="none"))
            svc2 = ParameterService(store2)
            restore_server_state(store2, svc2, str(tmp_path))
            s2, bound = serve(store2, port=port, service=svc2)
            assert bound == port, "could not rebind the old port"
            holder["server2"], holder["store2"] = s2, store2
            restarted.set()

        inner_push = client._call["PushGradrients"]

        def push_with_kill(request, timeout=None):
            # The 3rd push becomes the in-flight gradient: snapshot (2
            # applies + their journal), stop the server, and let the send
            # hit the dead socket.
            push_with_kill.calls += 1
            if push_with_kill.calls == 3 and not killed.is_set():
                save_store(store1, str(tmp_path),
                           journal_fn=svc1.journal_snapshot)
                server1.stop(grace=None)
                killed.set()
            return inner_push(request, timeout=timeout)

        push_with_kill.calls = 0
        client._call["PushGradrients"] = push_with_kill

        t = threading.Thread(target=restart_after_kill, daemon=True)
        t.start()
        w.start()
        w.join(timeout=300)
        t.join(timeout=120)
        assert killed.is_set() and restarted.is_set()
        try:
            assert not w.is_alive()
            assert w.result.error is None, w.result.error
            assert w.result.reconnects == 1
            store2 = holder["store2"]
            # Exactly-once across the restart: 3 epochs x 6 batches, K=2
            # -> 9 boundary pushes; 2 applied pre-crash (snapshotted), the
            # in-flight 3rd reconciled by repush after the resume, the
            # rest on the new server. No double-applies: the restored
            # step (2) plus post-restart applies equals 9 exactly.
            assert w.result.pushes_accepted == 9
            assert store2.stats.gradients_processed == 7
            assert store2.global_step == 9
            # The worker kept reporting telemetry: reconnect counter > 0
            # (cumulative — the process-global registry shares the
            # worker=0 instrument across this test's parametrizations).
            assert w._tm_reconnect.value >= 1
        finally:
            if "server2" in holder:
                holder["server2"].stop(grace=None)
            client.close()

    def test_reconnect_disabled_keeps_terminal_failure(self, tiny_model):
        """reconnect_timeout=0 (default): a dead server still fails the
        worker terminally — no silent behavior change for existing runs."""
        from distributed_parameter_server_for_ml_training_tpu.data import (
            synthetic_cifar100)
        from distributed_parameter_server_for_ml_training_tpu.ps import (
            PSWorker, WorkerConfig)

        model, flat, store = self._model_store(tiny_model)
        server, port = serve(store, port=0)
        client = RemoteStore(f"localhost:{port}", rpc_timeout=2.0,
                             rpc_retries=1, rpc_backoff=0.05)
        ds = synthetic_cifar100(n_train=64, n_test=16, num_classes=10)
        w = PSWorker(client, model, ds,
                     WorkerConfig(batch_size=16, num_epochs=3,
                                  augment=False, eval_each_epoch=False))

        def kill_soon():
            while store.stats.gradients_processed < 1:
                time.sleep(0.005)
            server.stop(grace=None)

        t = threading.Thread(target=kill_soon, daemon=True)
        t.start()
        w.start()
        w.join(timeout=120)
        t.join(timeout=30)
        assert not w.is_alive()
        assert w.result.error is not None
        assert w._session_lost(w.result.error) is not None
        client.close()

    def test_repush_viability_policy(self, tiny_model):
        """Discard-or-push staleness semantics for the stranded gradient."""
        from distributed_parameter_server_for_ml_training_tpu.data import (
            synthetic_cifar100)
        from distributed_parameter_server_for_ml_training_tpu.ps import (
            PSWorker, WorkerConfig)

        model, _, store = self._model_store(tiny_model, mode="async",
                                            staleness_bound=3)
        ds = synthetic_cifar100(n_train=32, n_test=16, num_classes=10)
        w = PSWorker(store, model, ds, WorkerConfig())
        assert w._repush_viable(old_fetched=5, server_step=7) is True
        assert w._repush_viable(old_fetched=5, server_step=9) is False
        assert w._repush_viable(old_fetched=5, server_step=4) is False
        store.config.mode = "sync"
        assert w._repush_viable(old_fetched=5, server_step=40) is True
        assert w._repush_viable(old_fetched=5, server_step=4) is False


class TestChannelLifecycle:
    """ISSUE 9 satellite: ``reset_channel`` must close the abandoned gRPC
    channel BEFORE replacing it — each leaked channel keeps an OS socket
    and its worker thread alive, so a worker riding many reconnects grows
    file descriptors without bound."""

    def test_repeated_reconnects_do_not_grow_open_channels(self, monkeypatch):
        created, closed = [], []
        real_insecure_channel = grpc.insecure_channel

        class TrackedChannel:
            def __init__(self, inner):
                self._inner = inner

            def close(self):
                if self not in closed:
                    closed.append(self)
                return self._inner.close()

            def __getattr__(self, name):
                return getattr(self._inner, name)

        def tracked(address, *args, **kwargs):
            ch = TrackedChannel(real_insecure_channel(address, *args,
                                                     **kwargs))
            created.append(ch)
            return ch

        monkeypatch.setattr(grpc, "insecure_channel", tracked)
        client = RemoteStore("localhost:1", rpc_retries=1,
                             rpc_backoff=0.01, rpc_timeout=1.0)
        assert len(created) == 1
        for _ in range(5):
            client.reset_channel()
        assert len(created) == 6
        # Every abandoned channel was closed at the moment it was
        # replaced; only the newest stays open.
        assert closed == created[:-1]
        client.close()
        assert closed == created


class TestShardedExactlyOnce:
    """ISSUE 9 satellite: the exactly-once machinery is PER SHARD — each
    primary journals only its own key subset, a push token survives its
    shard's kill+restart even when the shard map was refreshed in
    between, and zombie-token ordering holds independently on every
    shard."""

    def _shard(self, i, n=2, register=True):
        from distributed_parameter_server_for_ml_training_tpu.ps.sharding \
            import ShardInfo
        store = ParameterStore(
            {"w": np.ones(4, np.float32)},
            StoreConfig(mode="sync", total_workers=1, push_codec="none",
                        shard_index=i, shard_count=n))
        if register:
            store.register_worker()
        svc = ParameterService(store, sharding=ShardInfo(
            i, n, [f"localhost:{7000 + j}" for j in range(n)]))
        return store, svc

    def test_push_token_spans_map_refresh_and_shard_restart(self, tmp_path):
        """Per shard: apply a push, bump the shard-map version via a
        replica announce (the refresh the token must span), snapshot,
        kill, restore a fresh process with the SAME shard identity — the
        client's retry must replay from the journal, not re-apply, and
        the restarted primary must serve the map on ``have_shard_map``."""
        for i in range(2):
            store1, svc1 = self._shard(i, register=False)
            rmeta, _ = unpack_msg(svc1.register_worker(
                pack_msg({"worker_name": "w"}), None))
            # Shard map rides the registration reply (the capability).
            v0 = rmeta["shard_map"]["version"]
            assert rmeta["shard_map"]["shards"][i]["shard_id"] == i

            req = _push_request(rmeta["worker_id"], f"sh{i}:1", 0.5)
            m1, _ = unpack_msg(svc1.push_gradrients(req, None))
            assert m1["accepted"] and store1.global_step == 1

            # A replica announce lands between the apply and the retry:
            # the map version moves while the token is outstanding.
            svc1.fetch_parameters(pack_msg(
                {"replica": {"shard_id": i, "address": "localhost:9909"},
                 "have_step": 1}), None)
            assert svc1.sharding.version > v0

            path = tmp_path / f"shard{i}"
            save_store(store1, str(path), journal_fn=svc1.journal_snapshot)
            # The shard primary dies; a new process with the same
            # identity restores its OWN checkpoint+journal.
            store2, svc2 = self._shard(i)
            step, journal_n = restore_server_state(store2, svc2, str(path))
            assert (step, journal_n) == (1, 1)

            # Retry (same bytes) replays across the restart+refresh: no
            # double-apply on this shard.
            m2, _ = unpack_msg(svc2.push_gradrients(req, None))
            assert m2.get("duplicate") is True and m2["accepted"]
            assert store2.global_step == 1
            np.testing.assert_array_equal(store2.parameters["w"],
                                          store1.parameters["w"])

            # The restarted primary republishes its map via the same
            # delta handshake the refresh used.
            fmeta, _ = unpack_msg(svc2.fetch_parameters(
                pack_msg({"have_shard_map": 0}), None))
            assert fmeta["shard_map"]["shard_count"] == 2
            assert fmeta["shard_map"]["shards"][i]["shard_id"] == i

    def test_zombie_token_ordering_holds_per_shard(self):
        """The zombie-token scenario on every shard of a 2-shard
        topology: push n:1, then n:2; a late zombie n:1 must neither
        re-apply nor evict n:2's record on ITS shard."""
        for i in range(2):
            store, svc = self._shard(i)
            r1 = _push_request(0, f"zs{i}:1", 0.5)
            r2 = _push_request(0, f"zs{i}:2", 0.25, fetched_step=1)
            m1, _ = unpack_msg(svc.push_gradrients(r1, None))
            m2, _ = unpack_msg(svc.push_gradrients(r2, None))
            assert m1["accepted"] and m2["accepted"]
            assert store.global_step == 2
            w_after = store.parameters["w"].copy()

            mz, _ = unpack_msg(svc.push_gradrients(r1, None))
            assert mz.get("duplicate") is True
            assert mz.get("stale_token") is True
            assert store.global_step == 2
            np.testing.assert_array_equal(store.parameters["w"], w_after)

            mr, _ = unpack_msg(svc.push_gradrients(r2, None))
            assert mr.get("duplicate") is True and mr["accepted"]
            assert not mr.get("stale_token")
            assert store.global_step == 2
            np.testing.assert_array_equal(store.parameters["w"], w_after)

    def test_push_token_survives_handoff_and_recipient_restart(
            self, tmp_path):
        """ISSUE 11: exactly-once must span a LIVE slot-range handoff
        (docs/SHARDING.md "Migration protocol") and then the recipient's
        own crash — the donor's journal travels with the params, the
        recipient snapshots it as its own, and the pre-handoff token
        still answers ``duplicate`` after the recipient restarts."""
        from distributed_parameter_server_for_ml_training_tpu.ps.sharding \
            import ShardInfo, key_slot
        i = 0
        while not 16 <= key_slot(f"hk{i}") < 32:
            i += 1
        k = f"hk{i}"

        def shard(idx, params):
            store = ParameterStore(params, StoreConfig(
                mode="sync", total_workers=1, push_codec="none",
                shard_index=idx, shard_count=2))
            store.register_worker()
            svc = ParameterService(store, sharding=ShardInfo(
                idx, 2, ["a:1", "b:2"]))
            return store, svc

        donor_store, donor_svc = shard(0, {k: np.ones(4, np.float32)})
        req = pack_msg(
            {"worker_id": 0, "fetched_step": 0, "push_token": "hand:1"},
            encode_tensor_dict({k: np.full(4, 0.5, np.float32)}))
        m1, _ = unpack_msg(donor_svc.push_gradrients(req, None))
        assert m1["accepted"] and donor_store.global_step == 1
        applied = donor_store.parameters[k].copy()

        # Handoff [16,32) to shard 1: params + journal move together.
        emeta, payload = unpack_msg(donor_svc.reshard(
            pack_msg({"op": "export", "slot_lo": 16, "slot_hi": 32}),
            None))
        rec_store, rec_svc = shard(1, {})
        imeta, _ = unpack_msg(rec_svc.reshard(
            pack_msg({"op": "import", "journal": emeta["journal"]},
                     payload), None))
        assert imeta["adopted"] == 1 and imeta["journal_loaded"] >= 1
        for svc in (donor_svc, rec_svc):
            svc.reshard(pack_msg({"op": "apply_ranges",
                                  "ranges": [[0, 16], [16, 64]],
                                  "map_version": 9}), None)
        donor_svc.reshard(pack_msg({"op": "commit", "slot_lo": 16,
                                    "slot_hi": 32}), None)

        # The recipient dies and restores from ITS snapshot — which now
        # journals the donor's pre-handoff outcome as its own.
        save_store(rec_store, str(tmp_path),
                   journal_fn=rec_svc.journal_snapshot)
        rec_store2, rec_svc2 = shard(1, {})
        step, journal_n = restore_server_state(rec_store2, rec_svc2,
                                               str(tmp_path))
        assert journal_n >= 1

        m2, _ = unpack_msg(rec_svc2.push_gradrients(req, None))
        assert m2.get("duplicate") is True and m2["accepted"]
        np.testing.assert_array_equal(rec_store2.parameters[k], applied)
        assert rec_store2.global_step == step   # replay moved nothing


class TestFaultInjection:
    def test_same_seed_same_schedule(self):
        spec = "seed=11;push.unavailable@p=0.3;fetch.delay=0.01@every=4"
        a = FaultInjector(spec).schedule_preview("PushGradrients", 50)
        b = FaultInjector(spec).schedule_preview("PushGradrients", 50)
        assert a == b
        assert any(x is not None for x in a)
        # the delay rule fires on its own op's call index
        d = FaultInjector(spec).schedule_preview("FetchParameters", 8)
        assert [x for x in d if x is not None] == [("delay", 0.01)] * 2

    def test_scripted_indices_are_exact(self):
        fi = FaultInjector("push.drop_reply@n=2,5;fetch.deadline@every=3")
        got = [fi.decide("PushGradrients") for _ in range(6)]
        assert [g.kind if g else None for g in got] == \
            [None, "drop_reply", None, None, "drop_reply", None]
        got_f = [fi.decide("FetchParameters") for _ in range(6)]
        assert [g.kind if g else None for g in got_f] == \
            [None, None, "deadline", None, None, "deadline"]

    def test_bad_specs_rejected(self):
        for bad in ["", "push.frobnicate@p=0.1", "push.unavailable@p=1.5",
                    "nosuchop.delay@every=2", "push.unavailable@n=0",
                    "push.unavailable", "seed=1"]:
            with pytest.raises(ValueError):
                FaultInjector(bad)

    def test_client_faults_exercise_retry_layer(self):
        """Injected UNAVAILABLE rides the real retry path; injected
        drop_reply (apply happened, reply lost) rides the dedupe path —
        the store must end with exactly one apply per distinct push."""
        store = ParameterStore(
            {"w": np.ones(8, np.float32)},
            StoreConfig(mode="async", total_workers=1, push_codec="none",
                        staleness_bound=100))
        server, port = serve(store, port=0)
        try:
            client = RemoteStore(
                f"localhost:{port}", rpc_backoff=0.01,
                faults="push.unavailable@n=1;push.drop_reply@n=3")
            wid, _ = client.register_worker("chaos")
            # push 1: injected UNAVAILABLE -> retried (call 2) -> applied
            assert client.push(wid, {"w": np.full(8, 0.5, np.float32)}, 0)
            assert store.stats.gradients_processed == 1
            # push 2: call 3 applies server-side, reply dropped; call 4 is
            # the retry -> journal replays accepted, NO second apply.
            assert client.push(wid, {"w": np.full(8, 0.5, np.float32)}, 1)
            assert store.stats.gradients_processed == 2
            assert store.global_step == 2
            client.close()
        finally:
            server.stop(grace=None)

    def test_session_lost_error_raised_after_budget(self):
        client = RemoteStore("localhost:1", rpc_retries=1, rpc_backoff=0.01,
                             rpc_timeout=1.0)
        with pytest.raises(SessionLostError):
            client.fetch(0)

    @pytest.mark.parametrize("then", ["reply", "session_lost"])
    def test_cancelled_in_flight_is_transient(self, then):
        """A server stopped without grace answers the call in flight
        CANCELLED. The client cancels nothing itself, so that is the server
        going away: retried like UNAVAILABLE, and past the budget it is
        ``SessionLostError`` (what the reconnect machine acts on), never a
        bare ``RpcError``."""
        import grpc

        from distributed_parameter_server_for_ml_training_tpu.comms.faults \
            import InjectedRpcError
        store = ParameterStore(
            {"w": np.ones(8, np.float32)},
            StoreConfig(mode="async", total_workers=1, push_codec="none"))
        server, port = serve(store, port=0)
        try:
            client = RemoteStore(f"localhost:{port}", rpc_retries=1,
                                 rpc_backoff=0.01)
            wid, _ = client.register_worker("cancelled")
            real, calls = client._call["FetchParameters"], []

            def fetch_call(request, timeout=None):
                calls.append(len(calls))
                if len(calls) == 1:
                    raise InjectedRpcError(grpc.StatusCode.CANCELLED,
                                           "the server stopped")
                if then == "session_lost":
                    raise InjectedRpcError(grpc.StatusCode.UNAVAILABLE,
                                           "and stayed away")
                return real(request, timeout=timeout)

            client._call["FetchParameters"] = fetch_call
            if then == "reply":
                params, step = client.fetch(wid)
                assert step == 0
                np.testing.assert_array_equal(params["w"],
                                              np.ones(8, np.float32))
            else:
                with pytest.raises(SessionLostError):
                    client.fetch(wid)
            assert len(calls) == 2      # one retry: the whole budget
            client.close()
        finally:
            server.stop(grace=None)


class TestTenantJournalIsolation:
    """Per-job checkpoint lineage across a restart (docs/TENANCY.md):
    each job's snapshot journals ONLY its own push tokens, and the
    restarted server's dedupe stays per-tenant."""

    def _rig(self):
        from distributed_parameter_server_for_ml_training_tpu.ps.tenancy \
            import JobManager, parse_jobs_spec
        primary = ParameterStore(
            {"w": np.ones(4, np.float32)},
            StoreConfig(mode="async", total_workers=1, push_codec="none"))
        jobs = JobManager(primary,
                          parse_jobs_spec("joba:mode=async;jobb:mode=async"))
        svc = ParameterService(primary, jobs=jobs)
        wids = {}
        for j in ("joba", "jobb"):
            reply, _ = unpack_msg(svc.register_worker(
                pack_msg({"job": j}), None))
            wids[j] = reply["worker_id"]
        return jobs, svc, wids

    @staticmethod
    def _push(svc, wid, job, token, value):
        return unpack_msg(svc.push_gradrients(pack_msg(
            {"worker_id": wid, "fetched_step": 0, "push_token": token,
             "job": job},
            encode_tensor_dict({"w": np.full(4, value, np.float32)})),
            None))[0]

    def test_per_job_journal_replays_only_its_tenant(self, tmp_path):
        import functools

        jobs, svc, wids = self._rig()
        assert self._push(svc, wids["joba"], "joba", "n:1",
                          0.5)["accepted"]
        assert self._push(svc, wids["jobb"], "jobb", "n:1",
                          0.25)["accepted"]
        # joba's lineage directory persists joba's journal ONLY.
        save_store(jobs.store_for("joba"), str(tmp_path / "job-joba"),
                   journal_fn=functools.partial(svc.journal_snapshot,
                                                job="joba"))
        _, meta = load_store_record(str(tmp_path / "job-joba"))
        assert meta["job"] == "joba"
        journal = meta["push_journal"]
        assert len(journal) == 1  # zero cross-job leakage, byte-level

        # Restart: fresh stores, fresh service, journal loaded back.
        jobs2, svc2, wids2 = self._rig()
        from distributed_parameter_server_for_ml_training_tpu.checkpoint \
            import restore_store
        restore_store(jobs2.store_for("joba"),
                      str(tmp_path / "job-joba"))
        assert svc2.load_journal(journal) == 1
        # joba's retry replays the journaled outcome — no re-apply.
        m = self._push(svc2, wids2["joba"], "joba", "n:1", 0.5)
        assert m.get("duplicate") is True and m["accepted"]
        assert jobs2.store_for("joba").global_step == 1
        # jobb never had its journal restored: the same token APPLIES
        # there (fresh tenant, fresh dedupe namespace).
        m = self._push(svc2, wids2["jobb"], "jobb", "n:1", 0.25)
        assert not m.get("duplicate")
        assert jobs2.store_for("jobb").global_step == 1
