"""gRPC client: a remote ParameterStore with the in-process interface.

`RemoteStore` duck-types :class:`~..ps.store.ParameterStore`'s worker-facing
API (register_worker / fetch / push / job_finished), so
:class:`~..ps.worker.PSWorker` runs unchanged against a server on another
host — the reference's worker/server split (worker.py:199-231) without
Fargate.

Reference parity: registration retries 5x with exponential backoff
(worker.py:215-229); fp16 push compression happens client-side
(worker.py:264-268) when the server's codec asks for it; channel options
match worker.py:203-209.

Beyond the reference: the HOT RPCs (Fetch/Push/JobFinished) carry a
deadline and bounded retry on transient failures (round-4 VERDICT item 7).
The reference's worker dies on any mid-epoch RPC blip (worker.py:270-311
has no retry); this framework has elastic membership and heartbeats, so
surviving blips completes that story — a worker that retries through a
flicker keeps its slot, and membership updates keep flowing via the
piggybacked Fetch replies (reshard happens at the next epoch boundary).
Retried pushes are exactly-once: every push carries a unique ``push_token``
(the request bytes — token included — are packed once and retried
verbatim), and the server replays the recorded outcome for a token it has
already seen instead of re-applying the gradient
(comms/service.py:push_gradrients). Without the token a reply lost AFTER a
sync round completed would re-stash that gradient into the next round as a
stale duplicate (round-4 ADVICE finding).
"""

from __future__ import annotations

import json
import time

import grpc
import numpy as np

from ..telemetry import current_wire_trace, now as _tnow, trace_span

from .service import (GRPC_OPTIONS, SERVICE_NAME, RawJSON, pack_msg,
                      unpack_msg)

#: Transient codes worth retrying; anything else (e.g. INVALID_ARGUMENT,
#: UNIMPLEMENTED) indicates a real protocol problem and raises immediately.
#: CANCELLED is what a server stopped without grace answers the call that
#: was in flight; this client cancels no call of its own, so it can only
#: mean the server went away.
RETRYABLE_CODES = frozenset({
    grpc.StatusCode.UNAVAILABLE,
    grpc.StatusCode.DEADLINE_EXCEEDED,
    grpc.StatusCode.RESOURCE_EXHAUSTED,
    grpc.StatusCode.CANCELLED,
})


class SessionLostError(ConnectionError):
    """Transient failures outlived the retry budget: the server is most
    likely down or restarting. This replaces the old terminal behavior
    (the last ``grpc.RpcError`` escaping and killing the worker): it is a
    distinct, catchable signal the worker's reconnect state machine
    (`ps/worker.py:PSWorker._recover_session`) acts on — re-register for a
    fresh id, re-fetch at the restored server step, reconcile the
    in-flight gradient (docs/ROBUSTNESS.md). The last wire error rides as
    ``__cause__``."""


class _RemoteConfig:
    """Server-side StoreConfig facts the client learns at registration.
    PSWorker duck-types ``store.config`` for the elastic flag
    (ps/worker.py:_compute_shard); this is the remote half of that
    contract."""

    def __init__(self):
        self.elastic = False
        self.mode = "sync"
        self.learning_rate = 0.1
        # Advertised at registration; the reconnect reconciliation uses it
        # to decide discard-vs-repush for an in-flight gradient without a
        # wasted round trip (docs/ROBUSTNESS.md).
        self.staleness_bound = 5


class RemoteStore:
    """Client-side stand-in for ParameterStore over gRPC."""

    #: fetch() returns fp32 regardless of the server's fetch codec — the
    #: decompress happens HERE (client side); PSWorker._fetch_params checks
    #: this to avoid a second full-parameter cast per fetch.
    decompresses_fetches = True

    def __init__(self, address: str = "localhost:8000",
                 register_retries: int = 5,
                 rpc_timeout: float = 60.0,
                 rpc_retries: int = 3,
                 rpc_backoff: float = 0.5,
                 faults=None,
                 job: str | None = None):
        self.address = address
        #: Tenancy (docs/TENANCY.md): the job this client asks to join at
        #: registration. None joins the server's default job. The value
        #: is re-adopted from the registration reply's echo (the server
        #: may degrade an unknown/garbled id to the default job), and
        #: attached to every push/fetch envelope ONLY once the server
        #: advertises the ``jobs`` capability — a legacy server never
        #: sees the key (the delta_fetch gating discipline).
        self.job = job
        self.supports_jobs = False
        self.register_retries = register_retries
        self.rpc_timeout = rpc_timeout
        self.rpc_retries = rpc_retries
        self.rpc_backoff = rpc_backoff
        # Deterministic client-side fault injection (comms/faults.py):
        # a spec string (or prebuilt FaultInjector) interposes between the
        # retry layer and the channel, so injected faults exercise the
        # real backoff/reconnect machinery. Env DPS_FAULTS_CLIENT applies
        # fleet-wide without code changes (chaos drills).
        import os as _os
        if faults is None:
            faults = _os.environ.get("DPS_FAULTS_CLIENT") or None
        if faults is not None and isinstance(faults, str):
            from .faults import FaultInjector
            faults = FaultInjector(faults, side="client")
        self.faults = faults
        self._channel = None
        self._build_channel()
        # The most recent push's (token, payload, fetched_step): after a
        # session loss the reconnect path re-sends it VERBATIM except for
        # the worker id (repush_last) — same token means a push the
        # crashed server already applied and journaled replays as a
        # duplicate instead of double-applying.
        self._last_push: tuple[str, bytes, int] | None = None
        #: filled in at registration from the server's config; PSWorker reads
        #: these to apply the fp16 cast client-side before push
        #: (worker.py:264-268) and decompress after fetch.
        self.push_codec = "none"
        self.fetch_codec = "none"
        #: True once the server advertises the delta-fetch capability at
        #: registration; fetch(have_step=...) is only sent when set (an old
        #: server would silently ignore the field and ship the full model,
        #: which is correct but wasteful — gating keeps intent explicit).
        self.supports_delta_fetch = False
        #: True once the server advertises trace-context propagation at
        #: registration (same gating discipline as delta fetch,
        #: docs/WIRE_PROTOCOL.md): the trace field is only attached to
        #: push frames / fetch meta when the peer said it understands it.
        self.supports_trace_context = False
        #: True once the server advertises the health-report capability at
        #: registration (it runs a cluster monitor; docs/OBSERVABILITY.md).
        self.supports_health_report = False
        #: True once the server advertises compressed-domain aggregation
        #: (docs/WIRE_PROTOCOL.md): it accepts quantized payloads
        #: (int8/int4/topk) without decoding and publishes per-layer
        #: gradient scales. Same gating discipline as delta_fetch.
        self.supports_compressed_domain = False
        #: True once the server advertises the directive channel
        #: (docs/ROBUSTNESS.md "Self-healing"): its fetch/push reply meta
        #: may carry server->worker control directives. This client
        #: advertises the capability in its register request; either side
        #: missing it degrades to a directive-less wire.
        self.supports_directives = False
        #: True once the server advertises CRC verification on push
        #: frames (docs/WIRE_PROTOCOL.md "Checksum trailer"): pushes are
        #: then encoded with the 4-byte CRC-32 trailer and a corrupt
        #: frame is REFUSED server-side instead of silently applying.
        #: Gated because a legacy server would mistake the trailer for
        #: buffer slack — same degradation discipline as delta_fetch.
        self.supports_checksum = False
        #: Directives received but not yet taken by the worker loop, plus
        #: the highest seq seen (the dedupe/ack watermark — the server
        #: re-attaches outstanding directives every reply until acked).
        self._pending_directives: list[dict] = []  # guarded by: self._wire_lock
        self._directive_last_seq = 0  # guarded by: self._wire_lock
        #: Server-published per-layer gradient ABSMAX table + version,
        #: cached from the registration reply and refreshed off fetch
        #: reply meta (the client sends its version as ``have_qscales``;
        #: the server attaches the table only when newer).
        self._qscales: dict[str, float] = {}  # guarded by: self._wire_lock
        self._qscale_step = 0  # guarded by: self._wire_lock
        #: Zero-arg callable returning the worker's current health report
        #: (a small JSON-able dict) or None. PSWorker installs its own
        #: snapshot builder here after registration; when set AND the
        #: server advertised the capability, every fetch (incl. heartbeat
        #: pings) and push carries the report in the envelope meta. Legacy
        #: combinations — no provider, or a server that never advertised —
        #: attach nothing, so heartbeats degrade to plain pings.
        self.health_provider = None
        #: Optional zero-arg callable returning a monotonic REVISION for
        #: the provider's current report. When installed (PSWorker bumps
        #: it on every report mutation), the JSON encode of the report is
        #: cached per revision and spliced into the envelope as a
        #: pre-encoded fragment (RawJSON) — heartbeat pings at replica-
        #: refresh cadence were re-serializing an unchanged report per
        #: RPC. Without it every attach re-encodes (legacy behavior).
        self.health_revision = None
        # (revision, RawJSON) — the heartbeat thread's pings and the
        # comms thread's pushes both consult/refresh this cache.
        self._health_enc: tuple | None = None  # guarded by: self._wire_lock
        #: Server-published shard map (docs/SHARDING.md), adopted from the
        #: registration reply (its presence IS the capability) and
        #: refreshed off fetch reply meta delta-gated on the version the
        #: client sends back as ``have_shard_map``. None against an
        #: unsharded server — the wire stays single-server.
        self.shard_map = None
        self._shard_map_version = 0
        #: Keys the last push reply reported DISOWNED (docs/SHARDING.md
        #: "Migration protocol"): the primary's map moved while this
        #: client pushed on a cached one, so that slice never applied
        #: there. The fan-out store re-routes it to the current owner
        #: under a fresh token; a plain RemoteStore caller may re-push or
        #: drop (one async gradient slice, same cost as a staleness
        #: reject).
        self.last_disowned: list[str] = []
        self.config = _RemoteConfig()
        # Last membership seen on the wire (elastic servers piggyback it on
        # Register/Fetch replies). Workers fetch at least once per K-step
        # window, so by an epoch boundary this reflects recent churn.
        self._membership: list[int] = []
        # Wire accounting (the reference logged pickled payload sizes at
        # the server; here the client counts the payloads of SUCCESSFUL
        # RPCs — experiments/run_wire_matrix.py turns these into MB/s).
        # Lock: the heartbeat thread's fetch races the training thread's
        # push (gRPC releases the GIL), and lost read-modify-writes would
        # silently undercount.
        import threading

        self._wire_lock = threading.Lock()
        self.wire_bytes_out = 0  # guarded by: self._wire_lock
        self.wire_bytes_in = 0  # guarded by: self._wire_lock
        self.rpc_counts: dict[str, int] = {}  # guarded by: self._wire_lock
        # Push-dedupe token source: a per-client nonce + counter makes every
        # push's token unique across client restarts too (a replacement
        # worker reusing an elastic slot must not collide with its
        # predecessor's last token).
        import uuid

        self._push_nonce = uuid.uuid4().hex[:12]
        self._push_count = 0
        # Live telemetry (telemetry/): per-RPC latency spans + wire byte
        # counters into the process registry, alongside the run-local wire
        # accounting above (wire_stats feeds METRICS_JSON exit rows; the
        # registry feeds the live snapshot stream / Prometheus endpoint).
        from ..telemetry import get_registry
        reg = self._telemetry = get_registry()
        self._tm_rpc: dict[str, tuple] = {}
        for name in ["RegisterWorker", "PushGradrients", "FetchParameters",
                     "JobFinished", "Reshard", "SubmitJob"]:
            self._tm_rpc[name] = (
                reg.histogram("dps_rpc_client_seconds", rpc=name),
                reg.counter("dps_rpc_client_bytes_total", rpc=name,
                            direction="out"),
                reg.counter("dps_rpc_client_bytes_total", rpc=name,
                            direction="in"),
                reg.counter("dps_rpc_client_calls_total", rpc=name,
                            outcome="ok"),
                reg.counter("dps_rpc_client_calls_total", rpc=name,
                            outcome="retry"),
                reg.counter("dps_rpc_client_calls_total", rpc=name,
                            outcome="error"),
            )
        # Delta-fetch replies answered NOT_MODIFIED (header-only) — the
        # client-side twin of dps_store_fetch_not_modified_total.
        self._tm_fetch_nm = reg.counter(
            "dps_rpc_client_fetch_not_modified_total")

    def _invoke(self, name: str, request: bytes):
        """Call RPC ``name`` with a deadline, retrying transient failures
        (RETRYABLE_CODES) up to ``rpc_retries`` times with exponential
        backoff. Non-transient codes raise immediately."""
        hist, b_out, b_in, c_ok, c_retry, c_err = self._tm_rpc[name]
        delay = self.rpc_backoff
        for attempt in range(self.rpc_retries + 1):
            t0 = _tnow()
            # One trace span per ATTEMPT (not per logical call): a retried
            # RPC's trace tree shows each wire round trip, and the error
            # attr on a failed attempt marks exactly where time went.
            with trace_span("rpc.client", rpc=name, attempt=attempt) as sp:
                try:
                    reply = self._call[name](request,
                                             timeout=self.rpc_timeout)
                except grpc.RpcError as e:
                    # Failed attempts record their latency too — a
                    # deadline expiry spent real wall time, and dropping
                    # it would bias the distribution toward the happy
                    # path.
                    hist.observe(_tnow() - t0)
                    code = e.code() if callable(getattr(e, "code", None)) \
                        else None
                    # Mark the span even when the retry path SWALLOWS the
                    # exception (the span exits cleanly then, so the
                    # automatic error attr would not fire) — a retry
                    # storm's post-mortem must show which attempts burned
                    # the time.
                    sp.attrs["error"] = (code.name if code is not None
                                         else type(e).__name__)
                    if code not in RETRYABLE_CODES:
                        c_err.inc()
                        raise
                    if attempt >= self.rpc_retries:
                        # Transient failures outlived the budget: the
                        # server is down or restarting. Escalate as the
                        # catchable session-loss signal (the worker's
                        # reconnect state machine takes it from here)
                        # rather than a bare RpcError the caller can only
                        # die on.
                        c_err.inc()
                        raise SessionLostError(
                            f"{name} failed with {code.name} after "
                            f"{attempt + 1} attempts against "
                            f"{self.address}") from e
                    c_retry.inc()
                else:
                    hist.observe(_tnow() - t0)
                    with self._wire_lock:
                        self.wire_bytes_out += len(request)
                        self.wire_bytes_in += len(reply)
                        self.rpc_counts[name] = \
                            self.rpc_counts.get(name, 0) + 1
                    b_out.inc(len(request))
                    b_in.inc(len(reply))
                    c_ok.inc()
                    return reply
            time.sleep(delay)
            delay *= 2

    def _build_channel(self) -> None:
        """(Re)build the channel + method stubs + fault wrappers — the ONE
        place the method list and channel options are wired, shared by
        construction and ``reset_channel`` so the two can never drift."""
        self._channel = grpc.insecure_channel(self.address,
                                              options=GRPC_OPTIONS)
        ident = lambda b: b  # noqa: E731
        self._call = {
            name: self._channel.unary_unary(
                f"/{SERVICE_NAME}/{name}",
                request_serializer=ident, response_deserializer=ident)
            for name in ["RegisterWorker", "PushGradrients",
                         "FetchParameters", "JobFinished", "Reshard",
                         "SubmitJob"]
        }
        if self.faults is not None:
            from .faults import install_client_faults
            install_client_faults(self, self.faults)

    def reset_channel(self) -> None:
        """Tear down and rebuild the gRPC channel + method stubs.

        A channel that was connected to a server process that DIED can
        stay wedged in connect-failure backoff even after a replacement
        is listening on the same port (observed: every attempt fails
        'Timeout occurred: FD Shutdown' against a live listener, while a
        fresh channel connects instantly). The worker's reconnect state
        machine calls this before each re-registration attempt. Client-
        side fault injection survives the reset (same injector, same
        schedule state, re-installed over the fresh stubs); ad-hoc test
        wrappers around the old stubs do not — by the time a reset
        happens their work (killing a server at call N) is done.

        Closes the abandoned channel BEFORE building its replacement:
        close() releases the old channel's sockets/fds synchronously, so
        a worker that reconnects many times (flapping network, chaos
        drills) holds at most one channel at a time. The old order —
        build first, close after — left a window per reset where two
        channels were live, and an exception from _build_channel leaked
        the old one entirely (tests/test_recovery.py pins the no-growth
        invariant)."""
        old, self._channel = self._channel, None
        try:
            old.close()
        except Exception:  # noqa: BLE001 — a dead channel may complain
            pass
        self._build_channel()

    def wire_stats(self) -> dict:
        """Cumulative client-side wire accounting (bytes + per-RPC counts
        of successful calls); PSWorker merges this into its METRICS_JSON
        row."""
        with self._wire_lock:
            return {"wire_bytes_out": self.wire_bytes_out,
                    "wire_bytes_in": self.wire_bytes_in,
                    "rpc_counts": dict(self.rpc_counts)}

    def _note_membership(self, reply_meta: dict) -> None:
        m = reply_meta.get("active_workers")
        if m is not None:
            self._membership = [int(w) for w in m]

    def _note_directives(self, reply_meta: dict) -> None:
        """Collect piggybacked server->worker directives off a reply
        (capability-gated; docs/ROBUSTNESS.md). Dedupe by seq — the
        server re-attaches outstanding directives until acked, so the
        same directive may arrive on several replies. Malformed entries
        are dropped; directives must never fail the RPC that carried
        them."""
        if not self.supports_directives:
            # Never negotiated: a directive-shaped key from a confused
            # peer must not steer this worker (cap-gate discipline).
            return
        ds = reply_meta.get("directives")
        if not isinstance(ds, list):
            return
        with self._wire_lock:
            for d in ds:
                if not isinstance(d, dict):
                    continue
                try:
                    seq = int(d["seq"])
                except (KeyError, TypeError, ValueError):
                    continue
                if seq <= self._directive_last_seq \
                        or not isinstance(d.get("action"), str):
                    continue
                self._directive_last_seq = seq
                self._pending_directives.append(dict(d))

    def take_directives(self) -> list[dict]:
        """Drain the pending directives (worker loop, step boundaries)."""
        with self._wire_lock:
            out, self._pending_directives = self._pending_directives, []
            return out

    def _attach_directive_ack(self, meta: dict) -> None:
        if self.supports_directives:
            # Under the lock: the heartbeat thread's fetch replies may
            # advance the watermark concurrently with a push's attach.
            with self._wire_lock:
                meta["directives_ack"] = self._directive_last_seq

    def _note_qscales(self, reply_meta: dict) -> None:
        """Adopt a piggybacked shared-scale table (register/fetch reply
        meta). A malformed table degrades to the cached one — scales are
        an optimization hint, never worth failing an RPC over."""
        if not self.supports_compressed_domain:
            # Scales only exist under compressed-domain aggregation; an
            # ungated adopt would cache a table nothing consumes.
            return
        qs = reply_meta.get("qscales")
        if not isinstance(qs, dict):
            return
        try:
            table = {str(k): float(v) for k, v in qs.items()}
            step = int(reply_meta.get("qscale_step", 0))
        except (TypeError, ValueError):
            return
        # One lock write for the PAIR: the heartbeat thread's ping can
        # adopt a refresh while the training thread quantizes against
        # gradient_scales(); without the lock the reader could pair the
        # new table with the old version stamp (or vice versa) and
        # desync from the server's dequant scales.
        with self._wire_lock:
            self._qscales = table
            self._qscale_step = step

    def gradient_scales(self) -> tuple[dict[str, float], int]:
        """Client-side cache of the server's per-layer gradient absmax
        table (PSWorker quantizes against it; docs/WIRE_PROTOCOL.md)."""
        with self._wire_lock:
            return dict(self._qscales), self._qscale_step

    def _note_shard_map(self, reply_meta: dict) -> None:
        """Adopt a piggybacked shard map (register/fetch reply meta).
        Validated before adoption; a garbled or older map degrades to the
        cached one — routing must never regress off a bad refresh."""
        m = reply_meta.get("shard_map")
        if m is None:
            return
        from ..ps.sharding import validate_shard_map
        try:
            norm = validate_shard_map(m)
        except ValueError:
            return
        if self.shard_map is None \
                or norm["version"] >= self._shard_map_version:
            self.shard_map = norm
            self._shard_map_version = norm["version"]

    def membership_snapshot(self) -> list[int]:
        """Client-side view of the server's live membership (sorted ids),
        as of the most recent Register/Fetch reply. Empty until the first
        reply from an elastic server."""
        return list(self._membership)

    def register_worker(self, worker_name: str = "",
                        retries: int | None = None) -> tuple[int, int]:
        """Retry x5 with exponential backoff (worker.py:215-229).
        ``retries`` overrides the constructor budget — the reconnect state
        machine passes 1 and paces its own backoff against the overall
        reconnect window instead."""
        hist, b_out, b_in, c_ok, c_retry, c_err = \
            self._tm_rpc["RegisterWorker"]
        delay = 1.0
        last_err = None
        register_retries = (self.register_retries if retries is None
                            else max(1, int(retries)))
        for attempt in range(register_retries):
            t0 = _tnow()
            try:
                # ``capabilities`` advertises what THIS client can act on
                # (directives flow server->worker); an old server ignores
                # the field (docs/ROBUSTNESS.md). The requested job rides
                # the same envelope: a pre-tenancy server ignores it and
                # the worker lands in the only job there is.
                req_meta = {"worker_name": worker_name,
                            "capabilities": ["directives"]}
                if self.job is not None:
                    req_meta["job"] = str(self.job)
                request = pack_msg(req_meta)
                # Deadline like the hot RPCs: an undeadlined registration
                # against a half-up server would hang the worker (and the
                # reconnect state machine) indefinitely.
                raw = self._call["RegisterWorker"](request,
                                                   timeout=self.rpc_timeout)
                hist.observe(_tnow() - t0)
                b_out.inc(len(request))
                b_in.inc(len(raw))
                c_ok.inc()
                reply, _ = unpack_msg(raw)
                self.push_codec = reply.get("push_codec", "none")
                self.fetch_codec = reply.get("fetch_codec", "none")
                self.supports_delta_fetch = bool(
                    reply.get("delta_fetch", False))
                self.supports_trace_context = bool(
                    reply.get("trace_context", False))
                self.supports_health_report = bool(
                    reply.get("health_report", False))
                self.supports_compressed_domain = bool(
                    reply.get("compressed_domain", False))
                self.supports_directives = bool(
                    reply.get("directives", False))
                self.supports_checksum = bool(
                    reply.get("checksum", False))
                # Tenancy handshake (docs/TENANCY.md): adopt the job the
                # server placed us in — it may differ from the request
                # (garbled/unknown ids degrade to the default job), and
                # every subsequent envelope must carry the SERVER's
                # answer, not our wish.
                self.supports_jobs = bool(reply.get("jobs", False))
                if self.supports_jobs:
                    self.job = reply.get("job") or self.job
                # A fresh registration (incl. session resume against a
                # restarted server) starts a fresh directive stream: the
                # new server's seqs restart from 1, so a stale watermark
                # would suppress every delivery.
                # Registration is the negotiation point: drop any cached
                # scale table before adopting the reply's. A crash-
                # RESTORED server restarts its scale versions from 0 — a
                # stale higher version kept across session resume would
                # make have_qscales suppress every refresh until the new
                # server's version caught up.
                with self._wire_lock:
                    self._pending_directives = []
                    self._directive_last_seq = 0
                    self._qscales, self._qscale_step = {}, 0
                self._note_qscales(reply)
                # Same discipline for the shard map: a restarted primary's
                # map versions restart from 1, so the cached version must
                # not suppress the fresh map's adoption.
                self.shard_map, self._shard_map_version = None, 0
                self._note_shard_map(reply)
                self.config.elastic = bool(reply.get("elastic", False))
                self.config.mode = reply.get("mode", "sync")
                self.config.learning_rate = float(
                    reply.get("learning_rate", 0.1))
                self.config.staleness_bound = int(
                    reply.get("staleness_bound", 5))
                self._note_membership(reply)
                return int(reply["worker_id"]), int(reply["total_workers"])
            except grpc.RpcError as e:
                hist.observe(_tnow() - t0)
                # The LAST failed attempt is an error (the caller sees
                # ConnectionError), not a retry — dashboards alert on it.
                if attempt == register_retries - 1:
                    c_err.inc()
                else:
                    c_retry.inc()
                    time.sleep(delay)
                    delay *= 2
                last_err = e
        raise ConnectionError(
            f"registration failed after {register_retries} attempts: "
            f"{last_err}")

    def _attach_job(self, meta: dict) -> None:
        """Label an outbound envelope with this client's job
        (capability-gated: only after the server advertised ``jobs`` at
        registration — a legacy server never sees the key, the
        delta_fetch discipline; docs/TENANCY.md)."""
        if self.supports_jobs and self.job:
            meta["job"] = str(self.job)

    def _attach_health(self, meta: dict) -> None:
        """Piggyback the worker's current health report on an outbound
        fetch/push envelope (capability-gated; docs/OBSERVABILITY.md).
        A provider failure degrades to a report-less message — the health
        layer must never fail the RPC that would have carried it."""
        if not self.supports_health_report or self.health_provider is None:
            return
        rev = None
        if self.health_revision is not None:
            try:
                rev = self.health_revision()
            except Exception:  # noqa: BLE001
                rev = None
        if rev is not None:
            with self._wire_lock:
                cached = self._health_enc
            if cached is not None and cached[0] == rev:
                meta["health"] = cached[1]
                return
        try:
            report = self.health_provider()
        except Exception:  # noqa: BLE001
            return
        if isinstance(report, dict) and report:
            if rev is None:
                meta["health"] = report
                return
            enc = RawJSON(json.dumps(report))
            with self._wire_lock:
                self._health_enc = (rev, enc)
            meta["health"] = enc

    def fetch(self, worker_id: int | None = None,
              have_step: int | None = None
              ) -> tuple[dict[str, np.ndarray], int]:
        """Fetch params (+ step). With ``have_step`` (and a server that
        advertised ``delta_fetch``), a server whose step hasn't advanced
        replies NOT_MODIFIED — returned as ``({}, step)`` with
        ``step == have_step`` — and the caller keeps its current params;
        the round trip costs a header instead of the full model."""
        from .wire import decode_tensor_dict
        meta = {} if worker_id is None else {"worker_id": worker_id}
        self._attach_job(meta)
        if worker_id is not None:
            self._attach_health(meta)
            self._attach_directive_ack(meta)
        if have_step is not None and self.supports_delta_fetch:
            meta["have_step"] = int(have_step)
        if self.supports_compressed_domain:
            # Scale-table delta handshake: the server attaches qscales to
            # the reply only when its version is newer than this.
            with self._wire_lock:
                meta["have_qscales"] = self._qscale_step
        if self.shard_map is not None:
            # Shard-map delta handshake (docs/SHARDING.md): the server
            # attaches a map only when its version is newer than this.
            meta["have_shard_map"] = self._shard_map_version
        if self.supports_trace_context:
            # A fetch request carries no tensor frame, so the trace
            # context rides the envelope meta (docs/WIRE_PROTOCOL.md);
            # None (tracing off / no open span) attaches nothing.
            wt = current_wire_trace()
            if wt is not None:
                meta["trace"] = wt
        reply = self._invoke("FetchParameters", pack_msg(meta))
        rmeta, payload = unpack_msg(reply)
        self._note_membership(rmeta)
        self._note_qscales(rmeta)
        self._note_directives(rmeta)
        self._note_shard_map(rmeta)
        if rmeta.get("not_modified"):
            self._tm_fetch_nm.inc()
            return {}, int(rmeta["global_step"])
        with trace_span("worker.codec", stage="decode"):
            params = decode_tensor_dict(payload)
            if self.fetch_codec == "fp16":
                # serve --fetch-codec: the server halves the params-in
                # wire term (the reference's dominant cost,
                # server.py:222); restore fp32 here so callers never see
                # compressed dtypes. Wire accounting above already
                # counted the COMPRESSED reply. (PSWorker sees
                # decompresses_fetches and does NOT cast again.)
                from ..ops.compression import fp16_decompress
                params = fp16_decompress(params)
            elif self.fetch_codec == "bf16":
                from ..ops.compression import bf16_decompress
                params = bf16_decompress(params)
        return params, int(rmeta["global_step"])

    def push(self, worker_id: int, gradients: dict, fetched_step: int) -> bool:
        """Encode and send as-is: the caller (PSWorker._push) applies the
        codec, so compressed bytes hit the wire exactly once."""
        from .wire import encode_tensor_dict
        self._push_count += 1
        # Trace context rides the v2 FRAME header (capability-gated): the
        # request bytes are packed once — token and trace included — and
        # retried verbatim, so every retry carries the same span identity.
        # The same object is duplicated into the envelope meta so the
        # server's wrapper reads it without re-parsing the frame header
        # (docs/WIRE_PROTOCOL.md); the frame field remains the wire
        # contract for peers that only speak frames.
        wt = current_wire_trace() if self.supports_trace_context else None
        token = f"{self._push_nonce}:{self._push_count}"
        meta = {"worker_id": worker_id, "fetched_step": fetched_step,
                "push_token": token}
        self._attach_job(meta)
        if wt is not None:
            meta["trace"] = wt
        self._attach_health(meta)
        self._attach_directive_ack(meta)
        payload = encode_tensor_dict(gradients, trace=wt,
                                     checksum=self.supports_checksum)
        # Recorded BEFORE the send: a push that dies mid-RPC is exactly
        # the one the reconnect path must be able to re-send verbatim.
        self._last_push = (token, payload, int(fetched_step))
        reply = self._invoke("PushGradrients", pack_msg(meta, payload))
        rmeta, _ = unpack_msg(reply)
        self._note_directives(rmeta)
        # A push that raced a live migration (docs/SHARDING.md "Migration
        # protocol") comes back with the PRIMARY'S fresh map plus the list
        # of keys it disowned rather than applied. Adopt the map first so
        # any re-route below already targets the new owner.
        self._note_shard_map(rmeta)
        if self.shard_map is not None:
            d = rmeta.get("disowned")
            self.last_disowned = \
                [str(k) for k in d] if isinstance(d, list) else []
        return bool(rmeta["accepted"])

    def reshard_op(self, op: str, payload: bytes = b"",
                   **fields) -> tuple[dict, bytes]:
        """Admin-plane Reshard RPC (docs/SHARDING.md "Migration
        protocol"): ``export`` / ``import`` / ``apply_ranges`` /
        ``commit`` against ONE primary. Returns the raw reply
        ``(meta, payload)`` — the coordinator (``cli reshard``) owns the
        protocol ordering and interprets the fields; this client only
        carries the envelope. Extra keyword fields (``slot_lo``,
        ``slot_hi``, ``ranges``, ``map_version``, ``journal``) pass
        through to the request meta verbatim."""
        request = pack_msg({"op": op, **fields}, payload)
        reply = self._invoke("Reshard", request)
        return unpack_msg(reply)

    def submit_job(self, spec: str) -> dict:
        """Admin-plane SubmitJob RPC (docs/TENANCY.md): declare a new
        job from a one-entry ``--jobs``-grammar spec string. Returns the
        reply meta ({"submitted", "index", "jobs"}). Single-job servers
        answer FAILED_PRECONDITION."""
        reply = self._invoke("SubmitJob", pack_msg({"job_spec": str(spec)}))
        meta, _ = unpack_msg(reply)
        return meta

    def drain_job(self, name: str) -> dict:
        """Admin-plane job drain (docs/TENANCY.md): remove a drained
        job and its per-job metric series server-side."""
        reply = self._invoke("SubmitJob", pack_msg({"drain_job": str(name)}))
        meta, _ = unpack_msg(reply)
        return meta

    def repush_last(self, worker_id: int) -> bool | None:
        """Re-send the most recent push — same token, same payload, same
        ``fetched_step`` — under (possibly) a new worker id. The session-
        resume reconciliation path: the server's dedupe table is keyed by
        the token's NONCE, not the worker id, so if the pre-crash server
        applied this push and journaled it, the replay answers
        ``duplicate`` from the journal instead of applying twice; if the
        apply was lost with the crash, it applies now. Returns the
        accepted outcome, or None when there is nothing to re-send."""
        if self._last_push is None:
            return None
        token, payload, fetched_step = self._last_push
        meta = {"worker_id": worker_id, "fetched_step": fetched_step,
                "push_token": token}
        self._attach_job(meta)
        reply = self._invoke("PushGradrients", pack_msg(meta, payload))
        rmeta, _ = unpack_msg(reply)
        return bool(rmeta["accepted"])

    def job_finished(self, worker_id: int) -> None:
        self._invoke("JobFinished", pack_msg({"worker_id": worker_id}))

    def close(self) -> None:
        self._channel.close()
