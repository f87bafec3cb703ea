"""Headline benchmark: CIFAR-100 ResNet-18 training throughput per chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N}
or, on ANY failure, a diagnostic JSON line instead of a bare traceback:
    {"ok": false, "stage": ..., "error": ..., "attempts": ...}

Baseline: the reference's single-machine trainer did one CIFAR-100 epoch
(50,000 images) in 1037.8 s on an M1 Mac CPU (BASELINE.md; reference
baseline/results/baseline_summary.json performance_metrics.epoch_1)
= 48.18 images/sec. ``vs_baseline`` is our throughput over that number.

The benchmarked step is the real training step (normalize + augment + fwd +
bwd + SGD update, bfloat16 compute). The epoch loop runs ON DEVICE via
``lax.scan`` over prefetched batches — one dispatch per window, so host
dispatch is paid once per window rather than once per step; completion is
confirmed by fetching the final loss scalar. Several windows are timed and
the best is reported.

This is a device benchmark: it needs an accelerator. :func:`acquire_backend`
retries a failed backend init with exponential backoff (~3 min budget); a run
that still finds no chip — init keeps failing, or JAX answers with CPU
devices — emits the ``{"ok": false, ...}`` line above and exits 1. There is
no CPU fallback: a CPU number must never be written under this metric's
name. ``DPS_BENCH_FAIL_INJECT=N`` makes the first N init attempts fail
(tests prove the retry and the diagnostic artifact).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

import jax

REFERENCE_IMAGES_PER_SEC = 50_000 / 1037.8  # M1 Mac CPU epoch time

#: attempts = retries + 1; sum(3 * 2^k, k<5) = 93 s of sleep + init time
#: keeps the whole acquisition under a ~3-minute budget.
INIT_RETRIES = 5
INIT_BACKOFF_S = 3.0

_fail_inject_remaining: int | None = None


def _fail_injection_due() -> bool:
    """Test hook: env DPS_BENCH_FAIL_INJECT=N fails the first N init
    attempts (process-wide), letting tests prove retry AND diagnostic
    behavior without a real backend flake."""
    global _fail_inject_remaining
    if _fail_inject_remaining is None:
        _fail_inject_remaining = int(
            os.environ.get("DPS_BENCH_FAIL_INJECT", "0"))
    if _fail_inject_remaining > 0:
        _fail_inject_remaining -= 1
        return True
    return False


def acquire_backend(retries: int = INIT_RETRIES,
                    backoff: float = INIT_BACKOFF_S,
                    sleep=time.sleep) -> list:
    """``jax.devices()`` with bounded retry + exponential backoff.

    A transient backend-init failure looks identical to a permanent one on
    the first call. Returns the device list, or raises the LAST error after
    exhausting retries (attempt count attached as ``.bench_attempts`` for
    the diagnostic record).
    """
    delay = backoff
    last_err: Exception | None = None
    for attempt in range(1, retries + 2):
        try:
            if _fail_injection_due():
                raise RuntimeError("injected backend init failure "
                                   "(DPS_BENCH_FAIL_INJECT)")
            devices = jax.devices()
            if attempt > 1:
                print(f"backend init succeeded on attempt {attempt}",
                      file=sys.stderr)
            return devices
        except Exception as e:  # jax raises RuntimeError subtypes here
            last_err = e
            if attempt > retries:
                break
            print(f"backend init attempt {attempt} failed ({e}); "
                  f"retrying in {delay:.0f}s", file=sys.stderr)
            sleep(delay)
            delay *= 2
    last_err.bench_attempts = retries + 1
    raise last_err


def require_accelerator(devices: list, attempts: int = 1) -> list:
    """Refuse CPU devices: xla_bridge can fail accelerator init WITHOUT
    raising (``jax.devices()`` then answers ``CpuDevice`` after a warning),
    and a CPU number under this benchmark's metric name would read as a
    chip number."""
    if devices[0].platform == "cpu":
        err = RuntimeError(
            f"no accelerator: jax.devices() answered {devices[0]!r}; "
            f"bench.py measures the chip and has no CPU fallback")
        err.bench_attempts = attempts
        raise err
    return devices


def emit_diagnostic(stage: str, err: Exception) -> None:
    """The always-written failure artifact: one parseable JSON line on
    stdout (where the success line would have gone), so the driver's
    captured BENCH_r*.json is never empty/garbage on failure."""
    print(json.dumps({
        "ok": False,
        "stage": stage,
        "error": f"{type(err).__name__}: {err}",
        "attempts": getattr(err, "bench_attempts", 1),
        "traceback_tail": traceback.format_exc().strip()
        .splitlines()[-3:],
    }))


def fetch_qps_probe(duration_s: float = 1.0, concurrency: int = 2):
    """Serve-path companion number: QPS of an in-process gRPC fetch loop
    against a small parameter store (full-model fetches, no tensor decode
    client-side). Single primary, no replicas — the matching
    ``shard_count``/``replica_count`` fields say so, and the sharded
    scale-out numbers live in experiments/results/sharding/ where the
    topology is real. Returns None on any failure: the serve-path probe
    must never cost the training-throughput record."""
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu.comms.loadgen \
        import run_loadgen
    from distributed_parameter_server_for_ml_training_tpu.comms.service \
        import ParameterService, serve
    from distributed_parameter_server_for_ml_training_tpu.ps.store import (
        ParameterStore, StoreConfig)

    try:
        params = {f"layer{i}/kernel": np.zeros((256, 64), np.float32)
                  for i in range(8)}
        store = ParameterStore(
            params, StoreConfig(mode="async", total_workers=1))
        server, port = serve(store, port=0,
                             service=ParameterService(store))
        try:
            res = run_loadgen([f"localhost:{port}"],
                              duration_s=duration_s,
                              concurrency=concurrency, mode="full")
            return res["qps"]
        finally:
            server.stop(grace=0.2)
    except Exception as e:  # noqa: BLE001 — probe is best-effort
        print(f"fetch-qps probe failed (recording null): {e}",
              file=sys.stderr)
        return None


def fleet_probe(ticks: int = 3) -> dict:
    """Fleet-observatory companion fields (ISSUE 16): what one collector
    tick costs against an in-process target — ``fleet_targets_scraped``
    (fresh targets in the last tick), ``fleet_scrape_ms`` (last tick's
    wall), ``fleet_series_count`` (ring series held after the ticks).
    A tiny self-scrape, not a fleet: the real multi-process numbers live
    in experiments/results/fleet/. Failure-hardened nulls like the
    fetch/lint probes — never a cost to the throughput record."""
    out = {"fleet_targets_scraped": None, "fleet_scrape_ms": None,
           "fleet_series_count": None}
    try:
        from distributed_parameter_server_for_ml_training_tpu.telemetry \
            .fleet import FleetCollector
        from distributed_parameter_server_for_ml_training_tpu.telemetry \
            .prometheus import start_metrics_server
        from distributed_parameter_server_for_ml_training_tpu.telemetry \
            .registry import LATENCY_BUCKETS, MetricsRegistry

        target_reg = MetricsRegistry()
        target_reg.counter("bench_fleet_probe_total").inc(7)
        h = target_reg.histogram("bench_fleet_probe_seconds",
                                 buckets=LATENCY_BUCKETS)
        for v in (0.001, 0.004, 0.02):
            h.observe(v)
        server, port = start_metrics_server(target_reg, port=0,
                                            addr="localhost")
        try:
            collector = FleetCollector([f"localhost:{port}"],
                                       interval_s=0.05, timeout_s=2.0,
                                       registry=MetricsRegistry())
            last = {}
            for _ in range(ticks):
                last = collector.tick()
            view = collector.view()
            out = {
                "fleet_targets_scraped":
                    view["scrape"]["targets_scraped"],
                "fleet_scrape_ms": last.get("scrape_ms"),
                "fleet_series_count": view["series_count"],
            }
        finally:
            server.shutdown()
    except Exception as e:  # noqa: BLE001 — probe is best-effort
        print(f"fleet probe failed (recording nulls): {e}",
              file=sys.stderr)
    return out


def fanout_probe(duration_s: float = 0.75, concurrency: int = 4) -> dict:
    """Fan-out-tree companion fields (ISSUE 17): a two-tier in-process
    chain (primary -> interior replica -> edge replica) under a short
    delta-poll storm — ``tree_depth`` (edge tier reached), ``fanout_qps``
    (edge-served delta QPS), ``coalesce_ratio`` (edge coalesced/polls).
    A miniature, not the drill: the depth-3 multi-process numbers live in
    experiments/results/fanout/. Failure-hardened nulls like the other
    probes — never a cost to the throughput record."""
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu.comms.loadgen \
        import run_loadgen
    from distributed_parameter_server_for_ml_training_tpu.comms.replica \
        import ReplicaServer
    from distributed_parameter_server_for_ml_training_tpu.comms.service \
        import ParameterService, serve
    from distributed_parameter_server_for_ml_training_tpu.ps.store import (
        ParameterStore, StoreConfig)

    out = {"tree_depth": None, "coalesce_ratio": None, "fanout_qps": None}
    server = interior = edge = None
    try:
        params = {f"layer{i}/kernel": np.zeros((256, 64), np.float32)
                  for i in range(8)}
        store = ParameterStore(
            params, StoreConfig(mode="async", total_workers=1))
        server, port = serve(store, port=0,
                             service=ParameterService(store))
        interior = ReplicaServer(f"localhost:{port}", port=0,
                                 poll_interval=0.05)
        iport = interior.start()
        edge = ReplicaServer(f"localhost:{port}", port=0,
                             poll_interval=0.05,
                             parent=f"localhost:{iport}")
        eport = edge.start()
        deadline = time.time() + 5.0
        while time.time() < deadline and not edge.view()["synced"]:
            time.sleep(0.02)
        res = run_loadgen([f"localhost:{eport}"], duration_s=duration_s,
                          concurrency=concurrency, mode="delta")
        view = edge.view()
        out = {"tree_depth": int(view.get("tier") or 1),
               "coalesce_ratio": round(
                   view["coalesced"] / max(1, view["polls"]), 3),
               "fanout_qps": res["qps"]}
    except Exception as e:  # noqa: BLE001 — probe is best-effort
        print(f"fanout probe failed (recording nulls): {e}",
              file=sys.stderr)
    finally:
        for rep in (edge, interior):
            if rep is not None:
                try:
                    rep.stop()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
        if server is not None:
            server.stop(grace=0.2)
    return out


def journal_probe(records: int = 400) -> dict:
    """Durable-journal companion fields (ISSUE 18): what one journal
    append costs against tmpfs-or-disk — ``journal_write_us`` (median
    per-record append wall, line-buffered path, no fsync) and
    ``journal_bytes_per_tick`` (bytes one realistic cumulative snapshot
    record costs on disk). Both LOWER-is-better in benchwatch's ledger
    (EXTRA_METRIC_FIELDS direction), gating docs/OBSERVABILITY.md's
    <2% overhead claim. Failure-hardened nulls like the other probes —
    never a cost to the throughput record."""
    import shutil
    import tempfile

    out = {"journal_write_us": None, "journal_bytes_per_tick": None}
    tmp = None
    try:
        from distributed_parameter_server_for_ml_training_tpu.telemetry \
            .journal import JournalWriter
        from distributed_parameter_server_for_ml_training_tpu.telemetry \
            .registry import LATENCY_BUCKETS, MetricsRegistry

        # A realistic per-tick payload: a registry snapshot the size a
        # serving process actually carries (a few counters/gauges plus
        # pinned-bucket latency histograms).
        reg = MetricsRegistry()
        for i in range(8):
            reg.counter("bench_journal_probe_total", stream=str(i)).inc(i)
            reg.gauge("bench_journal_probe_gauge", stream=str(i)).set(i)
            h = reg.histogram("bench_journal_probe_seconds",
                              buckets=LATENCY_BUCKETS, stream=str(i))
            for v in (0.001, 0.004, 0.02, 0.11):
                h.observe(v)
        payload = {"ts": time.time(), **reg.snapshot()}
        tmp = tempfile.mkdtemp(prefix="bench-journal-")
        writer = JournalWriter(tmp, role="bench",
                               registry=MetricsRegistry())
        walls = []
        for _ in range(records):
            t0 = time.perf_counter()
            writer.append("snapshot", payload)
            walls.append(time.perf_counter() - t0)
        writer.seal()
        total = sum(
            os.path.getsize(os.path.join(tmp, n))
            for n in os.listdir(tmp))
        walls.sort()
        out = {"journal_write_us":
               round(walls[len(walls) // 2] * 1e6, 2),
               "journal_bytes_per_tick": int(round(total / records))}
    except Exception as e:  # noqa: BLE001 — probe is best-effort
        print(f"journal probe failed (recording nulls): {e}",
              file=sys.stderr)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    return out


def lint_probe() -> dict:
    """Static-analysis companion fields: ``lint_clean`` (did the tree
    pass dpslint — live findings or a stale baseline mean False) and
    ``lint_runtime_s`` (what the analyzer costs, pinned < 5 s by
    tests/test_dpslint.py). Failure-hardened like the fetch probe: any
    analyzer error records ``{"lint_clean": null}`` and never costs the
    training-throughput record."""
    try:
        root = os.path.dirname(os.path.abspath(__file__))
        if root not in sys.path:
            sys.path.insert(0, root)
        from tools.dpslint.cli import run_lint
        res = run_lint(root)
        return {"lint_clean": res["exit_code"] == 0,
                "lint_runtime_s": res["runtime_s"]}
    except Exception as e:  # noqa: BLE001 — probe is best-effort
        print(f"lint probe failed (recording null): {e}", file=sys.stderr)
        return {"lint_clean": None, "lint_runtime_s": None}


def codec_probe(devices, reps: int = 3) -> dict:
    """Device-codec companion fields (ISSUE 14): throughput of the
    device-resident int8 quantize+pack over a synthetic multi-layer
    gradient tree — ``codec_mb_per_s`` (input fp32 MB over the best
    encode+finalize wall, the number benchwatch tracks once it has
    history), ``codec_seconds`` (that best wall), and ``codec_device``
    (the platform the encode actually ran on). Failure-hardened nulls like
    the fetch/lint probes — never a cost to the throughput record."""
    import numpy as np

    try:
        import jax.numpy as jnp

        from distributed_parameter_server_for_ml_training_tpu.ops \
            .device_codec import DeviceCodec

        rng = np.random.default_rng(3)
        # ~4 MB across mixed layer sizes.
        flat = {f"layer{i}/kernel":
                jnp.asarray(rng.normal(size=n).astype(np.float32))
                for i, n in enumerate([262144, 262144, 262144,
                                       131072, 65536, 16384, 384])}
        pre_mb = sum(v.size for v in flat.values()) * 4 / 1e6
        codec = DeviceCodec(error_feedback=False)
        plan = {k: "int8" for k in flat}
        codec.finalize(codec.encode(flat, plan=plan))  # compile warmup
        best = float("inf")
        for _ in range(reps):
            codec.reset()
            t0 = time.perf_counter()
            codec.finalize(codec.encode(flat, plan=plan))
            best = min(best, time.perf_counter() - t0)
        return {"codec_device": devices[0].platform,
                "codec_seconds": round(best, 6),
                "codec_mb_per_s": round(pre_mb / best, 1)}
    except Exception as e:  # noqa: BLE001 — probe is best-effort
        print(f"codec probe failed (recording null): {e}",
              file=sys.stderr)
        return {"codec_device": None, "codec_seconds": None,
                "codec_mb_per_s": None}


def run_bench(args) -> dict:
    stage = "backend_init"
    try:
        retries = getattr(args, "init_retries", INIT_RETRIES)
        devices = require_accelerator(
            acquire_backend(
                retries=retries,
                backoff=getattr(args, "init_backoff", INIT_BACKOFF_S)),
            attempts=retries + 1)

        stage = "build"
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributed_parameter_server_for_ml_training_tpu.models import (
            ResNet18)
        from distributed_parameter_server_for_ml_training_tpu.parallel import (
            make_mesh, make_sync_dp_step)
        from distributed_parameter_server_for_ml_training_tpu.train import (
            create_train_state, make_train_step, server_sgd)

        n_chips = len(devices)
        print(f"benchmarking on {devices} "
              f"(batch {args.batch_size} x {args.scan_steps} steps/window)",
              file=sys.stderr)

        if n_chips > 1:
            # Multi-chip: the real sync-DP step over a mesh of ALL chips, so
            # the per-chip number divides work that genuinely ran on every
            # chip.
            mesh = make_mesh(n_chips)
            model = ResNet18(num_classes=100, dtype=jnp.bfloat16,
                             axis_name="data")
            train_step = make_sync_dp_step(mesh, compression="bf16",
                                           augment=True)
            batch_sharding = NamedSharding(mesh, P(None, "data"))
        else:
            mesh = None
            model = ResNet18(num_classes=100, dtype=jnp.bfloat16)
            train_step = make_train_step(augment=True)
            batch_sharding = None

        state = create_train_state(model, jax.random.PRNGKey(0),
                                   server_sgd(0.1))

        def window(state, images, labels, key):
            """scan-steps training steps fully on device (prefetched
            batches)."""
            def body(carry, batch):
                st, k = carry
                xb, yb = batch
                st, metrics = train_step(st, xb, yb, k)
                return (st, k), metrics["loss"]

            (state, _), losses = jax.lax.scan(
                body, (state, key), (images, labels))
            return state, losses[-1]

        window = jax.jit(window, donate_argnums=0)

        rng = np.random.default_rng(0)
        images = jnp.asarray(rng.integers(
            0, 255, (args.scan_steps, args.batch_size, 32, 32, 3),
            dtype=np.uint8))
        labels = jnp.asarray(np.tile(
            np.arange(args.batch_size) % 100,
            (args.scan_steps, 1)).astype(np.int32))
        if batch_sharding is not None:
            images = jax.device_put(images, batch_sharding)
            labels = jax.device_put(labels, batch_sharding)
        key = jax.random.PRNGKey(1)

        # Warmup: compile + one full window.
        stage = "warmup_compile"
        state, loss = window(state, images, labels, key)
        _ = float(loss)

        stage = "timed_trials"
        best_dt = float("inf")
        timed_wall = 0.0
        # Goodput ledger over the timed trials (ISSUE 20 satellite b):
        # a private registry so the bench never pollutes the process
        # default; trial compute is spanned, everything else the loop
        # does (prints, min/max bookkeeping) lands in the residual —
        # goodput_fraction below 1.0 IS the harness overhead.
        from distributed_parameter_server_for_ml_training_tpu \
            .telemetry.goodput import GoodputAccount
        from distributed_parameter_server_for_ml_training_tpu \
            .telemetry.registry import MetricsRegistry as _GpRegistry
        gp = GoodputAccount(_GpRegistry())
        profile_ctx = contextlib.nullcontext()
        if getattr(args, "profile_dir", None):
            # Perf observatory (docs/OBSERVABILITY.md): bracket ONLY the
            # timed trials — warmup compile and the fetch probe stay out
            # of the dump so attribution reconciles against timed wall.
            from distributed_parameter_server_for_ml_training_tpu \
                .telemetry.profiler import capture
            profile_ctx = capture(args.profile_dir)
            print(f"profiler: tracing timed trials into "
                  f"{args.profile_dir}", file=sys.stderr)
        with profile_ctx:
            gp.start_wall()
            for trial in range(args.trials):
                t0 = time.perf_counter()
                with gp.span("compute"):
                    state, loss = window(state, images, labels, key)
                    final_loss = float(loss)  # forces the whole chain
                dt = time.perf_counter() - t0
                print(f"trial {trial}: {dt*1e3:.1f} ms, "
                      f"loss {final_loss:.4f}", file=sys.stderr)
                best_dt = min(best_dt, dt)
                timed_wall += dt
                gp.tick_wall()
        goodput_fraction = gp.fraction()
        if goodput_fraction is not None:
            goodput_fraction = round(goodput_fraction, 4)

        images_per_sec = args.scan_steps * args.batch_size / best_dt
        per_chip = images_per_sec / n_chips
        # Wire attribution (ISSUE 6 satellite): which gradient codec this
        # number was measured under, and the bytes the gradient exchange
        # moves per step — 2·(N-1)/N·payload for the ring all-reduce, 0 on
        # a single chip (no link crossed) — so BENCH_r* rounds can
        # attribute wire wins instead of conflating codec and kernel
        # changes.
        n_params = sum(int(np.prod(l.shape)) for l in
                       jax.tree_util.tree_leaves(state.params))
        grad_codec = "bf16" if n_chips > 1 else "none"
        el_bytes = {"none": 4, "bf16": 2, "fp16": 2, "int8": 1}[grad_codec]
        ring_bytes = (2 * (n_chips - 1) / n_chips * n_params * el_bytes
                      if n_chips > 1 else 0)
        # Perf-observatory companion fields (ISSUE 12): MFU from the
        # SINGLE step's compile-time cost analysis (never the scanned
        # window — XLA reports whole-program flops) and the fraction of
        # timed wall the profiler attributed to device/executable time.
        # Only computed when a profile was captured; both are
        # failure-hardened nulls, never a cost to the record.
        stage = "profile_attribution"
        mfu_value = None
        device_time_fraction = None
        attribution_basis = None
        if getattr(args, "profile_dir", None):
            from distributed_parameter_server_for_ml_training_tpu \
                .analysis.device_profile import attribute_profile
            from distributed_parameter_server_for_ml_training_tpu \
                .telemetry.profiler import compiled_cost
            from distributed_parameter_server_for_ml_training_tpu \
                .telemetry.profiler import mfu as mfu_of
            try:
                step_fn = train_step if hasattr(train_step, "lower") \
                    else jax.jit(train_step)
                cost = compiled_cost(
                    step_fn.lower(state, images[0], labels[0],
                                  key).compile())
                mfu_value = mfu_of(cost["flops"],
                                   args.scan_steps / best_dt,
                                   devices[0].device_kind, n_chips)
                if mfu_value is not None:
                    mfu_value = round(mfu_value, 4)
            except Exception as e:  # noqa: BLE001 — null, never a crash
                print(f"cost analysis failed (mfu recorded null): {e}",
                      file=sys.stderr)
            try:
                attributed = attribute_profile(args.profile_dir)
                prof = attributed["profile"]
                if timed_wall > 0 and prof["total_attributed_s"] > 0:
                    device_time_fraction = round(
                        prof["total_attributed_s"]
                        / (timed_wall * n_chips), 4)
                    attribution_basis = prof.get("basis")
                # Raw Chrome traces are scratch once attribution
                # succeeded (ISSUE 20 satellite f) — same prune policy
                # as `cli perf profile`: keep on failure for debugging.
                if prof.get("basis") not in (None, "none") \
                        and not attributed.get("parse_errors"):
                    from distributed_parameter_server_for_ml_training_tpu \
                        .telemetry.profiler import prune_capture
                    pruned = prune_capture(args.profile_dir)
                    if pruned:
                        print(f"profiler: pruned {len(pruned)} raw "
                              f"trace file(s) from {args.profile_dir}",
                              file=sys.stderr)
            except Exception as e:  # noqa: BLE001 — null, never a crash
                print(f"profile attribution failed (recording null): "
                      f"{e}", file=sys.stderr)

        stage = "fetch_probe"
        fetch_qps = None
        if not getattr(args, "no_fetch_probe", False):
            fetch_qps = fetch_qps_probe(
                duration_s=getattr(args, "fetch_probe_secs", 1.0))

        # Push-codec attribution (ISSUE 14): what the device-resident
        # quantize+pack sustains on this backend, so BENCH_r* rounds can
        # attribute wire-side wins separately from the train step.
        stage = "codec_probe"
        codec_fields = {"codec_device": None, "codec_seconds": None,
                        "codec_mb_per_s": None}
        if not getattr(args, "no_codec_probe", False):
            codec_fields = codec_probe(devices)

        # Fleet-observatory attribution (ISSUE 16): what one collector
        # scrape tick costs against an in-process target, so BENCH_r*
        # rounds can watch the observer's own overhead.
        stage = "fleet_probe"
        fleet_fields = {"fleet_targets_scraped": None,
                        "fleet_scrape_ms": None,
                        "fleet_series_count": None}
        if not getattr(args, "no_fleet_probe", False):
            fleet_fields = fleet_probe()

        # Fan-out-tree attribution (ISSUE 17): what a two-tier replica
        # chain serves and coalesces in-process, so BENCH_r* rounds can
        # attribute tree-serve wins separately from the flat serve path.
        stage = "fanout_probe"
        fanout_fields = {"tree_depth": None, "coalesce_ratio": None,
                         "fanout_qps": None}
        if not getattr(args, "no_fanout_probe", False):
            fanout_fields = fanout_probe()

        # Durable-journal attribution (ISSUE 18): what one telemetry
        # journal append costs, so BENCH_r* rounds can watch the
        # black-box recorder's own overhead (lower-is-better in
        # benchwatch).
        stage = "journal_probe"
        journal_fields = {"journal_write_us": None,
                          "journal_bytes_per_tick": None}
        if not getattr(args, "no_journal_probe", False):
            journal_fields = journal_probe()

        # Memory companion fields (ISSUE 20): peak device HBM from the
        # allocator stats (null on CPU — no memory_stats()) and peak
        # host RSS from /proc/self/status, the same samplers the
        # memory_growth health rule reads. Failure-hardened nulls.
        stage = "memory_probe"
        from distributed_parameter_server_for_ml_training_tpu \
            .telemetry.memory import read_device_memory, read_host_rss
        dev_mem = read_device_memory(devices[0]) or {}
        host_mem = read_host_rss() or {}

        result = {
            "metric": "cifar100_resnet18_train_images_per_sec_per_chip",
            "value": round(per_chip, 1),
            "unit": "images/sec/chip",
            "vs_baseline": round(per_chip / REFERENCE_IMAGES_PER_SEC, 2),
            "push_codec": grad_codec,
            "push_bytes_per_step": int(ring_bytes),
            # Serve-path attribution (docs/SHARDING.md): the topology the
            # fetch_qps probe ran against — here always one in-process
            # primary, zero replicas; the sharded numbers live in
            # experiments/results/sharding/.
            "shard_count": 1,
            "replica_count": 0,
            "fetch_qps": fetch_qps,
            # Elastic serve-tier attribution (ISSUE 11): the bench runs
            # against a static in-process topology, so these are zero by
            # construction — the elastic numbers live in
            # experiments/results/elastic_serve/. Non-zero values in a
            # record mean the topology moved DURING the measurement.
            "replica_count_live": 0,
            "autoscale_actions": 0,
            "canary_promotions": 0,
            "reshard_events": 0,
            # Robustness attribution (ISSUE 13): zero by construction for
            # the same reason — no coordinator crash/resume and no fault
            # injection run during a bench measurement; the chaos numbers
            # live in experiments/results/reshard_chaos/. Non-zero values
            # mean the measurement overlapped a recovery.
            "reshard_resumes": 0,
            "corrupt_frames_refused": 0,
            # Tenancy attribution (ISSUE 15): the bench measures a
            # single-tenant in-process store — one (default) job, no
            # admission throttling by construction; the multi-job QoS
            # numbers live in experiments/results/tenancy/. A non-zero
            # qos_throttled_total means the measurement ran against a
            # contended multi-job server (docs/TENANCY.md).
            "job_count": 1,
            "qos_throttled_total": 0,
            # Perf-observatory fields (ISSUE 12): null unless this run
            # captured a profile (--profile-dir). device_time_fraction is
            # attributed time / (timed wall x chips); the basis says
            # whether that attribution came from real device lanes or the
            # CPU backend's host-execute proxy (docs/OBSERVABILITY.md).
            "mfu": mfu_value,
            "device_time_fraction": device_time_fraction,
            "profile_attribution_basis": attribution_basis,
            # Device-codec attribution (ISSUE 14): see codec_probe.
            **codec_fields,
            # Fleet-observatory attribution (ISSUE 16): see fleet_probe.
            **fleet_fields,
            # Fan-out-tree attribution (ISSUE 17): see fanout_probe.
            **fanout_fields,
            # Durable-journal attribution (ISSUE 18): see journal_probe.
            **journal_fields,
            # Goodput observatory (ISSUE 20): productive fraction of the
            # timed-trial wall (compute spans / wall ticks — below 1.0
            # is harness overhead, tracked higher-is-better by
            # benchwatch) and the memory peaks at measurement end.
            "goodput_fraction": goodput_fraction,
            "peak_hbm_bytes": dev_mem.get("peak_bytes_in_use"),
            "host_rss_peak_bytes": host_mem.get("peak_rss_bytes"),
        }
        # Static-analysis attribution (ISSUE 10 satellite): whether the
        # tree this number was measured from passed dpslint, and what the
        # analyzer itself costs — a perf record from a tree with live
        # findings is flagged at the source instead of discovered later.
        stage = "lint_probe"
        result.update(lint_probe())
        return result
    except Exception as e:
        e.bench_stage = stage
        raise


def main() -> int:
    parser = argparse.ArgumentParser()
    # Batch 3072 x an 80-step scan window: one dispatch per window, so a
    # longer window amortizes per-dispatch host cost over more device
    # steps. Both values were chosen on the earlier installation; whether
    # they are still right is a perf_opt question for the ledger.
    parser.add_argument("--batch-size", type=int, default=3072)
    parser.add_argument("--scan-steps", type=int, default=80,
                        help="train steps per device-side scan window")
    parser.add_argument("--trials", type=int, default=5)
    parser.add_argument("--init-retries", type=int, default=INIT_RETRIES,
                        help="backend-init retries before the diagnostic "
                             "record is written")
    parser.add_argument("--init-backoff", type=float,
                        default=INIT_BACKOFF_S,
                        help="first retry delay (doubles per attempt)")
    parser.add_argument("--fetch-probe-secs", type=float, default=1.0,
                        help="duration of the serve-path fetch-QPS probe "
                             "recorded as fetch_qps")
    parser.add_argument("--no-fetch-probe", action="store_true",
                        help="skip the serve-path probe (fetch_qps "
                             "recorded as null)")
    parser.add_argument("--no-codec-probe", action="store_true",
                        help="skip the device-codec probe (codec_* "
                             "fields recorded as null)")
    parser.add_argument("--no-fanout-probe", action="store_true",
                        help="skip the two-tier replica fan-out probe "
                             "(tree_depth/coalesce_ratio/fanout_qps "
                             "record nulls)")
    parser.add_argument("--no-fleet-probe", action="store_true",
                        help="skip the fleet-collector probe (fleet_* "
                             "fields recorded as null)")
    parser.add_argument("--no-journal-probe", action="store_true",
                        help="skip the telemetry-journal probe "
                             "(journal_write_us/journal_bytes_per_tick "
                             "record nulls)")
    parser.add_argument("--profile-dir", default=None,
                        help="capture a jax.profiler trace of the timed "
                             "trials into this directory and record "
                             "mfu / device_time_fraction in the result "
                             "(parse with `cli perf profile`)")
    args = parser.parse_args()

    from distributed_parameter_server_for_ml_training_tpu.utils \
        .compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        result = run_bench(args)
    except Exception as e:
        emit_diagnostic(getattr(e, "bench_stage", "unknown"), e)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
