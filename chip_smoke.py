"""Proof that the system still starts on the chip: `python chip_smoke.py`.

Drives both exchange paths once through the normal CLI at the full width of
ResNet-18 / CIFAR-100-shaped input (bf16, per-worker batch 128, augmentation
on, a few steps), plus the Pallas kernels against their jax.numpy references:

    kernels     flash and short attention fwd/bwd, the ViT-B/16 gradient with
                the fused core against dense_core, int8 quantize family,
                DeviceCodec
    sync        cli train --mode sync --workers <chips>
    sync-int8   the same with --compression int8 (quantize inside shard_map)
    async       cli train --mode async --workers max(2, <chips>)
                --store-backend device (one worker per chip)
    wire        cli serve (host store, CPU) + cli worker (chip) over gRPC,
                int8 push codec, bf16 fetch codec

One process per chip: this parent never imports jax (nor the package, which
does); each phase is a child that owns the chip alone and has exited before
the next starts. The serve/worker pair is two children — the server is the
CPU-side one. Chip children run with JAX_PLATFORMS=tpu, so a missing chip is
an error from JAX, never a CPU run; the parent also checks the platform each
child reports about itself.

Only when every phase passed: exit code 0, a ``SUMMARY_JSON:`` line (per-phase
wall time and findings, ``"claim": null``) and then, as the last stdout line,
the result ``{"ok": true, "device": {"platform": ..., "kind": ..., "count":
...}}`` — those keys and no others, the device as JAX reported it. Any
failure: exit code 1, the reason on stderr, no result line. Children's full
output lands under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = "distributed_parameter_server_for_ml_training_tpu"
CLI = [sys.executable, "-m", f"{PKG}.cli"]
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
#: The whole script must end inside 1200 s, compilation included.
BUDGET_S = 1140.0
BATCH = 128   # per-worker batch (the reference's distributed default)
STEPS = 8     # steps per worker: --num-train = STEPS x global batch
TRAIN_ARGS = ["--synthetic", "--epochs", "1", "--num-test", "1000",
              "--emit-metrics"]
_T0 = time.monotonic()
_LIVE: dict[subprocess.Popen, str] = {}   # running child -> log name
#: The kernel child's checks, by name; the parent requires every one.
KERNEL_CHECKS = (
    "flash_fp32_T256", "flash_fp32_T256_causal", "flash_bf16_T4097",
    "flash_bf16_T1024_causal", "flash_heads_major_bf16_T4096_causal",
    "short_attn_bf16_T197",
    "vit_b16_grads_fused_vs_dense", "quantize_2359296",
    "quantize_non_multiple_of_128", "device_codec_resnet18_tree")


class PhaseFailed(Exception):
    pass


# -- process plumbing ---------------------------------------------------------

def _say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _env(chip: bool, extra: dict | None = None) -> dict:
    env = {**os.environ, **(extra or {})}
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    if chip:
        env["JAX_PLATFORMS"] = "tpu"
    return env


def _spawn(name: str, cmd: list[str], chip: bool,
           env: dict | None = None) -> subprocess.Popen:
    os.makedirs(LOG_DIR, exist_ok=True)
    out = open(os.path.join(LOG_DIR, f"{name}.out"), "w")
    err = open(os.path.join(LOG_DIR, f"{name}.err"), "w")
    proc = subprocess.Popen(cmd, cwd=HERE, env=_env(chip, env), stdout=out,
                            stderr=err, start_new_session=True)
    out.close()
    err.close()
    _LIVE[proc] = name
    return proc


def _kill(proc: subprocess.Popen) -> None:
    """Stop a child and everything it started (it leads its own session)."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    _LIVE.pop(proc, None)


def _read(name: str, stream: str) -> str:
    with open(os.path.join(LOG_DIR, f"{name}.{stream}"),
              errors="replace") as f:
        return f.read()


def _remaining() -> float:
    return BUDGET_S - (time.monotonic() - _T0)


def _wait(proc: subprocess.Popen, limit: float) -> None:
    """Wait for a child within the phase limit AND the script budget."""
    name = _LIVE[proc]
    try:
        rc = proc.wait(timeout=max(1.0, min(limit, _remaining())))
    except subprocess.TimeoutExpired:
        _kill(proc)
        raise PhaseFailed(f"{name}: still running after its time limit; "
                          f"killed\n{_tail(name)}") from None
    del _LIVE[proc]
    if rc != 0:
        raise PhaseFailed(f"{name}: exit code {rc}\n{_tail(name)}")


def _tail(name: str, lines: int = 40) -> str:
    err = _read(name, "err").strip().splitlines()[-lines:]
    return "\n".join(f"    {name}.err| {ln}" for ln in err)


def _tagged(text: str, tag: str) -> list[dict]:
    return [json.loads(m.group(1)) for m in
            re.finditer(rf"{tag}:\s*(\{{.*\}})", text)]


def _cache_dir() -> str:
    # The rule of utils/compile_cache.py, restated because importing the
    # package would import jax into this process.
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(HERE, ".jax_cache")


def _cache_entries() -> set:
    try:
        return set(os.listdir(_cache_dir()))
    except OSError:
        return set()


# -- what each phase must show ------------------------------------------------

def _require(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def _on_chip(row: dict, who: str, count: int | None = None) -> None:
    _require(row.get("platform") == "tpu",
             f"{who} reports platform={row.get('platform')!r}, not 'tpu'")
    if count is not None:
        _require(row.get("device_count") == count,
                 f"{who} saw {row.get('device_count')} devices, "
                 f"expected {count}")


def _finite_loss(row: dict, who: str) -> float:
    loss = row.get("final_train_loss")
    _require(isinstance(loss, float) and loss == loss
             and abs(loss) != float("inf"),
             f"{who}: final_train_loss={loss!r} is not finite")
    return loss


def _rows(name: str) -> tuple[list[dict], list[dict]]:
    """A child's exit METRICS_JSON rows: (server/trainer rows, worker
    rows)."""
    rows = _tagged(_read(name, "out"), "METRICS_JSON")
    return ([r for r in rows if "worker_id" not in r],
            [r for r in rows if "worker_id" in r])


def _trainer_rows(name: str, n_workers: int) -> tuple[dict, list[dict]]:
    servers, workers = _rows(name)
    _require(len(servers) == 1 and len(workers) == n_workers,
             f"{name}: {len(servers)} trainer and {len(workers)} worker "
             f"METRICS_JSON rows, expected 1 and {n_workers}")
    return servers[0], workers


def _device_report() -> dict | None:
    """What JAX found, as the kernel child said before anything else —
    read whether or not its checks then passed."""
    try:
        found = _tagged(_read("kernels", "out"), "DEVICE_JSON")
    except OSError:
        return None
    return found[0] if found else None


def phase_kernels() -> dict:
    """Pallas kernels, compiled (Mosaic), each against its jax.numpy
    reference on the same device. Also the device probe."""
    proc = _spawn("kernels", [sys.executable, os.path.abspath(__file__),
                              "--child-kernels"], chip=True)
    _wait(proc, 600)
    checks = _tagged(_read("kernels", "out"), "KERNEL_JSON")
    _on_chip(_device_report() or {}, "kernels child")
    passed = {c["check"] for c in checks if c["ok"]}
    _require(passed == set(KERNEL_CHECKS),
             f"kernels: missing or failed {set(KERNEL_CHECKS) - passed}\n"
             + "\n".join(f"    {json.dumps(c)}" for c in checks))
    return {"checks": checks}


def phase_sync(count: int, compression: str) -> dict:
    name = "sync" if compression == "bf16" else f"sync-{compression}"
    proc = _spawn(name, CLI + [
        "train", "--mode", "sync", "--workers", str(count),
        "--compression", compression, "--batch-size", str(BATCH),
        "--num-train", str(STEPS * BATCH * count)] + TRAIN_ARGS, chip=True)
    _wait(proc, 420)
    server, _ = _trainer_rows(name, count)
    _on_chip(server, f"{name} trainer", count)
    _require(server["mode"] == "sync" and server["total_workers"] == count,
             f"{name}: ran {server}")
    _require(server["global_steps_completed"] == STEPS,
             f"{name}: {server['global_steps_completed']} steps, "
             f"expected {STEPS}")
    return {"loss": _finite_loss(server, name), "devices": count}


def phase_async(count: int) -> dict:
    n_workers = max(2, count)   # one worker per chip; two share one chip
    proc = _spawn("async", CLI + [
        "train", "--mode", "async", "--workers", str(n_workers),
        "--store-backend", "device", "--batch-size", str(BATCH),
        "--num-train", str(STEPS * BATCH * n_workers)] + TRAIN_ARGS,
        chip=True)
    _wait(proc, 420)
    server, workers = _trainer_rows("async", n_workers)
    _on_chip(server, "async trainer", count)
    _require(server["total_parameter_updates"] > 0,
             f"async: store applied no update: {server}")
    for w in workers:
        who = f"async worker {w['worker_id']}"
        _on_chip(w, who)
        _finite_loss(w, who)
        _require(w["local_steps_completed"] == STEPS,
                 f"{who}: {w['local_steps_completed']} steps")
    device_ids = sorted(w["device_id"] for w in workers)
    _require(len(set(device_ids)) == count,
             f"async: {n_workers} workers computed on devices {device_ids}, "
             f"leaving some of the {count} chips idle")
    return {"updates": server["total_parameter_updates"],
            "worker_device_ids": device_ids}


def _served_port(server: subprocess.Popen, limit: float = 180) -> int:
    """The port a `cli serve --port 0` child says it listens on."""
    name = _LIVE[server]
    deadline = time.monotonic() + min(limit, _remaining())
    while True:
        m = re.search(r"parameter server up on :(\d+)", _read(name, "err"))
        if m:
            return int(m.group(1))
        if server.poll() is not None:
            raise PhaseFailed(f"{name}: exited {server.returncode} before "
                              f"serving\n{_tail(name)}")
        if time.monotonic() > deadline:
            raise PhaseFailed(f"{name}: never came up\n{_tail(name)}")
        time.sleep(0.25)


def phase_wire() -> dict:
    """The README's two-process recipe: the server takes no --platform
    flag — a host store pins its own process to the CPU backend, and this
    phase hangs into its time limit if it ever takes the chip instead."""
    server = _spawn("wire-serve", CLI + [
        "serve", "--mode", "async", "--workers", "1",
        "--store-backend", "python", "--push-codec", "int8",
        "--fetch-codec", "bf16", "--port", "0", "--emit-metrics"],
        chip=False)
    try:
        port = _served_port(server)
        worker = _spawn("wire-worker", CLI + [
            "worker", "--server", f"localhost:{port}",
            "--worker-name", "smoke-w0", "--batch-size", str(BATCH),
            "--num-train", str(STEPS * BATCH)] + TRAIN_ARGS, chip=True)
        _wait(worker, 420)
        # The server leaves on its own once its one worker said goodbye.
        _wait(server, 60)
    finally:
        _kill(server)
    up = re.search(r"parameter server up on .*", _read("wire-serve", "err"))
    _require("platform=cpu" in up.group(0),
             f"wire-serve did not report the CPU backend: {up.group(0)}")
    servers, _ = _rows("wire-serve")
    _, workers = _rows("wire-worker")
    _require(len(servers) == 1 and len(workers) == 1,
             f"wire: {len(servers)} server and {len(workers)} worker "
             f"METRICS_JSON rows, expected 1 and 1")
    srv, w = servers[0], workers[0]
    _require(srv["gradients_processed"] > 0,
             f"wire-serve processed no gradient: {srv}")
    _on_chip(w, "wire worker")
    _finite_loss(w, "wire worker")
    _require(w["local_steps_completed"] == STEPS,
             f"wire worker: {w['local_steps_completed']} steps")
    return {"gradients_processed": srv["gradients_processed"],
            "push_codec": "int8", "fetch_codec": "bf16"}


def result_line(device: dict) -> str:
    """The last stdout line of a passing run: exactly these keys, the
    device as the kernel child's ``device_fields()`` reported it."""
    return json.dumps({
        "ok": True,
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["device_kind"]),
                   "count": int(device["device_count"])}})


def main() -> int:
    phases: dict[str, dict] = {}
    failures: list[str] = []

    def run(name: str, fn, *args) -> None:
        before = _cache_entries()
        t0 = time.monotonic()
        try:
            if _remaining() <= 0:
                raise PhaseFailed(f"{name}: script budget spent")
            info = fn(*args)
            ok = True
        except PhaseFailed as e:
            info, ok = {}, False
            failures.append(name)
            _say(f"phase {name} FAILED: {e}")
        added = sorted(_cache_entries() - before)
        phases[name] = {"ok": ok, "seconds": round(
            time.monotonic() - t0, 1), "cache_entries_added": len(added),
            **{k: v for k, v in info.items() if k != "checks"}}
        print(f"PHASE {name}: {'ok' if ok else 'FAILED'} "
              f"{phases[name]['seconds']}s, {len(added)} new compile-cache "
              f"entries in {_cache_dir()}", flush=True)
        for entry in added:
            print(f"  + {entry}", flush=True)
        for check in info.get("checks", ()):
            print(f"  {json.dumps(check)}", flush=True)

    try:
        run("kernels", phase_kernels)
        device = _device_report()
        if device is None or device.get("platform") != "tpu":
            _say(f"the kernel child found no TPU (it reported {device}); "
                 f"nothing else ran")
            return 1
        count = device["device_count"]
        run("sync", phase_sync, count, "bf16")
        run("sync-int8", phase_sync, count, "int8")
        run("async", phase_async, count)
        run("wire", phase_wire)
    finally:
        for proc in list(_LIVE):
            _kill(proc)
    if failures:
        _say(f"FAILED phases: {', '.join(failures)}")
        return 1
    print("SUMMARY_JSON: " + json.dumps({
        "phases": phases,
        "total_seconds": round(time.monotonic() - _T0, 1),
        "claim": None}), flush=True)
    print(result_line(device), flush=True)
    return 0


# -- the kernel child (the only code here that imports jax) -------------------

def _child_kernels(only: tuple = ()) -> int:
    """Every kernel check, or those named in ``only`` (for a builder who
    iterates on one kernel; the parent always asks for all)."""
    import traceback
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu.models import (
        ResNet18, get_model)
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention as at, device_codec as dc)
    from distributed_parameter_server_for_ml_training_tpu.ops.compression \
        import ErrorFeedback, compress_push
    from distributed_parameter_server_for_ml_training_tpu.ops.pallas import (
        flash_attention as fa, quantize as qz, short_attention as sa)
    from distributed_parameter_server_for_ml_training_tpu.parallel \
        .ring_attention import dense_attention
    from distributed_parameter_server_for_ml_training_tpu.utils \
        .compile_cache import enable_compile_cache
    from distributed_parameter_server_for_ml_training_tpu.utils.metrics \
        import device_fields
    from distributed_parameter_server_for_ml_training_tpu.utils.pytree \
        import flatten_params

    enable_compile_cache()
    print("DEVICE_JSON: " + json.dumps(device_fields()), flush=True)
    if jax.default_backend() != "tpu":
        print(f"kernels need a TPU, JAX gave {jax.default_backend()}",
              file=sys.stderr)
        return 1
    # A pass must mean the kernel ran: no interpreter, no jnp stand-in.
    assert fa.INTERPRET is False and qz._on_tpu()
    assert sa.INTERPRET is False and at._on_tpu()
    mosaic = 'custom_call_target="tpu_custom_call"'
    failed = []

    def compiled(fn, *args):
        """(executable, compile seconds, Mosaic calls in the module)."""
        t0 = time.perf_counter()
        exe = jax.jit(fn).lower(*args).compile()
        return exe, time.perf_counter() - t0, exe.as_text().count(mosaic)

    def rel_err(got, want) -> float:
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        assert got.shape == want.shape and np.isfinite(got).all()
        return float(np.max(np.abs(got - want))
                     / max(float(np.max(np.abs(want))), 1e-30))

    def check(name):
        assert name in KERNEL_CHECKS, name

        def deco(fn):
            if only and name not in only:
                return
            t0 = time.perf_counter()
            try:
                info = fn()
            except Exception:  # noqa: BLE001 — reported, then exit code 1
                traceback.print_exc()
                failed.append(name)
                info = {"ok": False}
            print("KERNEL_JSON: " + json.dumps({
                "check": name, "ok": name not in failed,
                "seconds": round(time.perf_counter() - t0, 2), **info}),
                flush=True)
        return deco

    def flash_case(b, t, h, d, dtype, causal, tol):
        ks = jax.random.split(jax.random.PRNGKey(t), 4)
        q, k, v, cot = (jax.random.normal(kk, (b, t, h, d), dtype)
                        for kk in ks)

        def weighted(attn, q, k, v, cot):
            return jnp.sum(attn(q, k, v).astype(jnp.float32)
                           * cot.astype(jnp.float32))

        flash = partial(fa.flash_attention, causal=causal, use_pallas=True)
        fwd, fwd_s, fwd_calls = compiled(flash, q, k, v)
        bwd, bwd_s, bwd_calls = compiled(
            jax.grad(partial(weighted, flash), argnums=(0, 1, 2)),
            q, k, v, cot)
        assert fwd_calls >= 1 and bwd_calls >= 3, (fwd_calls, bwd_calls)
        out = fwd(q, k, v)
        grads = bwd(q, k, v, cot)
        # Reference: plain dense softmax attention in true fp32 matmuls,
        # one batch element at a time (its [H, T, T] scores are what the
        # flash kernel exists not to materialise).
        dense = partial(dense_attention, causal=causal)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda q, k, v, cot: (
                dense(q, k, v),
                jax.grad(partial(weighted, dense), argnums=(0, 1, 2))(
                    q, k, v, cot)))
            errs = {"o": 0.0, "dq": 0.0, "dk": 0.0, "dv": 0.0}
            for i in range(b):
                s = slice(i, i + 1)
                ro, rg = ref(q[s], k[s], v[s], cot[s])
                for key, got, want in zip(
                        errs, (out[s],) + tuple(g[s] for g in grads),
                        (ro,) + tuple(rg)):
                    errs[key] = max(errs[key], rel_err(got, want))
        assert max(errs.values()) <= tol, (errs, tol)
        return {"shape": [b, t, h, d], "dtype": jnp.dtype(dtype).name,
                "causal": causal, "compile_fwd_s": round(fwd_s, 2),
                "compile_bwd_s": round(bwd_s, 2), "mosaic_calls_fwd":
                fwd_calls, "mosaic_calls_bwd": bwd_calls, "tol": tol,
                "max_err_over_max_ref": {k: round(v, 5)
                                         for k, v in errs.items()}}

    # tests/test_flash_attention.py::test_pallas_path_on_tpu lives here:
    # the CPU suite can only run the kernels' jnp fallback. The tolerance
    # is the MXU's, not fp32's: at default precision Mosaic's fp32 dot
    # rounds its operands to bf16, as XLA's own fp32 einsum does on this
    # chip (measured 4e-3..6e-3 of the largest reference value, PERF.md).
    check("flash_fp32_T256")(
        lambda: flash_case(2, 256, 2, 64, jnp.float32, False, 2e-2))
    check("flash_fp32_T256_causal")(
        lambda: flash_case(2, 256, 2, 64, jnp.float32, True, 2e-2))
    # ViT-B/16 @1024 px (pads 4097 -> a block multiple; whole K and V of a
    # head in VMEM) and the causal ring-hop shape.
    check("flash_bf16_T4097")(
        lambda: flash_case(2, 4097, 12, 64, jnp.bfloat16, False, 3e-2))
    check("flash_bf16_T1024_causal")(
        lambda: flash_case(2, 1024, 12, 64, jnp.bfloat16, True, 3e-2))

    def heads_major_case(b, t, h, d, dv, tol):
        """The heads-major entry (q, k ``[B, H, T, D]``; v, o ``[B, T,
        H*Dv]`` read a head's lanes at a time by the kernels' block specs)
        against ``flash_attention`` on the same numbers as ``[B, T, H,
        D]``, causal, at latent attention's widths: the same kernels on the
        same blocks, so the outputs are equal and the gradients differ by
        rounding at most."""
        ks = jax.random.split(jax.random.PRNGKey(t), 4)
        q, k = (jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
                for kk in ks[:2])
        v, cot = (jax.random.normal(kk, (b, t, h, dv), jnp.bfloat16)
                  for kk in ks[2:])

        def bthd(q, k, v):
            return fa.flash_attention(q, k, v, causal=True, use_pallas=True)

        def heads_major(q, k, v):
            return fa.flash_attention_heads_major(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.reshape(b, t, h * dv), causal=True).reshape(b, t, h, dv)

        def both(fn, kernels):
            exe, seconds, calls = compiled(
                lambda q, k, v, cot: jax.value_and_grad(
                    lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                            * cot.astype(jnp.float32)),
                    argnums=(0, 1, 2))(q, k, v), q, k, v, cot)
            assert calls == kernels, calls
            return exe(q, k, v, cot), seconds

        (want_l, want), _s = both(bthd, 3)
        # one more: delta = rowsum(dO * O) a head, read out of [B, T, H*Dv]
        (got_l, got), seconds = both(heads_major, 4)
        errs = {key: rel_err(g, w) for key, g, w in
                zip(("dq", "dk", "dv"), got, want)}
        errs["o"] = rel_err(jax.jit(heads_major)(q, k, v),
                            jax.jit(bthd)(q, k, v))
        assert errs["o"] == 0.0 and max(errs.values()) <= tol, (errs, tol)
        return {"shape": [b, t, h, d, dv], "compile_s": round(seconds, 2),
                "tol": tol, "max_err_over_max_bthd": {
                    k: round(v, 6) for k, v in errs.items()}}

    def flash_kernel_ms(b, t, h, d, dv, calls=5):
        """Device ms a call of each flash kernel at the decoder LM's shape
        (q, k ``[B*H, T, D]``; v, dO ``[B, T, H*Dv]``, causal), each in a
        program of its own (the backward's other kernel is dropped with its
        unused results: one Mosaic call a program, asserted): the median
        duration of the kernel's events in a device trace of ``calls``
        runs. For a builder judging a change to a kernel body, parent
        beside change; no check reads it."""
        import glob
        import statistics
        import tempfile
        from jax.profiler import ProfileData

        ks = jax.random.split(jax.random.PRNGKey(t), 4)
        q, k = (jax.random.normal(kk, (b * h, t, d), jnp.bfloat16)
                for kk in ks[:2])
        v, do = (jax.random.normal(kk, (b, t, h * dv), jnp.bfloat16)
                 for kk in ks[2:])
        blk = fa.pick_block(t)

        def fwd(q, k, v):
            return fa._flash_fwd_impl(q, k, v, t, blk, blk, True,
                                      causal=True, v_heads=h)

        def bwd(*args):
            return fa._flash_bwd_impl(*args, t, blk, blk, True, causal=True,
                                      q_len=t, v_heads=h)

        o, lse = jax.jit(fwd)(q, k, v)
        grads = (q, k, v, do, lse, fa._delta_of_heads(do, o, h, blk, True))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = options.host_tracer_level = 0
        out = {}
        for name, fn, args in (
                ("flash_attention_fwd", fwd, (q, k, v)),
                ("flash_attention_bwd_dq", lambda *a: bwd(*a)[0], grads),
                ("flash_attention_bwd_dkv", lambda *a: bwd(*a)[1:], grads)):
            exe, _s, kernels = compiled(fn, *args)
            assert kernels == 1, (name, kernels)
            jax.block_until_ready(exe(*args))
            os.makedirs(LOG_DIR, exist_ok=True)
            with tempfile.TemporaryDirectory(dir=LOG_DIR) as trace_dir:
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                for _ in range(calls):
                    result = exe(*args)
                jax.block_until_ready(result)
                jax.profiler.stop_trace()
                (xplane,) = glob.glob(trace_dir + "/**/*.xplane.pb",
                                      recursive=True)
                ms = [e.duration_ns / 1e6
                      for plane in ProfileData.from_file(xplane).planes
                      if plane.name.startswith("/device:TPU:")
                      for line in plane.lines if line.name == "XLA Ops"
                      for e in line.events
                      if e.name.split(" = ")[0].lstrip("%").split(".")[0]
                      == name]
            assert len(ms) == calls, (name, len(ms))
            out[name] = round(statistics.median(ms), 3)
        return out

    check("flash_heads_major_bf16_T4096_causal")(
        lambda: {**heads_major_case(1, 4096, 4, 192, 128, 1e-2),
                 "kernel_ms_b4_h32": flash_kernel_ms(4, 4096, 32, 192, 128)})

    def short_case(b, t, h, d, tol):
        """The fused short-sequence kernel as ``attention_core`` calls it
        (``[B, T, 3*H*D]`` in, ``[B, T, H*D]`` out, ``d qkv`` back) against
        plain softmax attention in true fp32 matmuls, and ``dense_core``'s
        distance from the same reference beside it."""
        qkv = jax.random.normal(jax.random.PRNGKey(t), (b, t, 3 * h * d),
                                jnp.bfloat16)
        cot = jax.random.normal(jax.random.PRNGKey(t + 1), (b, t, h * d),
                                jnp.bfloat16)

        def weighted(attn, qkv, cot):
            return jnp.sum(attn(qkv).astype(jnp.float32)
                           * cot.astype(jnp.float32))

        def split(attn):
            def on_qkv(qkv):
                x = qkv.reshape(b, t, 3, h, d)
                return attn(x[:, :, 0], x[:, :, 1], x[:, :, 2]).reshape(
                    b, t, h * d)
            return on_qkv

        assert at.select_core(on_tpu=True, causal=False, dtype=qkv.dtype,
                              t=t, num_heads=h, head_dim=d) == "fused_short"
        fused = partial(at.attention_core, num_heads=h)
        both = lambda attn: lambda qkv, cot: (  # noqa: E731
            attn(qkv), jax.grad(partial(weighted, attn))(qkv, cot))
        exe, compile_s, calls = compiled(both(fused), qkv, cot)
        assert calls >= 2, calls
        # the kernels' names reach the compiled program (in a whole train
        # step they are the instructions' names, PERF.md section 3)
        text = exe.as_text()
        assert "short_attention_fwd" in text and "short_attention_bwd" in text
        got = exe(qkv, cot)
        dense_bf16 = jax.jit(both(split(at.dense_core)))(qkv, cot)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(both(split(dense_attention)))(
                qkv.astype(jnp.float32), cot.astype(jnp.float32))
        errs = {k: rel_err(g, w) for k, g, w in zip(("o", "dqkv"), got, want)}
        dense_errs = {k: rel_err(g, w)
                      for k, g, w in zip(("o", "dqkv"), dense_bf16, want)}
        assert max(errs.values()) <= tol, (errs, tol)
        return {"shape": [b, t, h, d], "compile_s": round(compile_s, 2),
                "mosaic_calls": calls, "tol": tol,
                "max_err_over_max_ref": {k: round(v, 5)
                                         for k, v in errs.items()},
                "dense_core_max_err_over_max_ref": {
                    k: round(v, 5) for k, v in dense_errs.items()}}

    # ViT-B/16 @224 px, the benchmark's shape: 197 tokens overhang the
    # kernel's 256-row blocks, so this is also the check that what VMEM
    # held beyond row 197 reaches no result.
    check("short_attn_bf16_T197")(
        lambda: short_case(8, 197, 12, 64, 3e-2))

    def vit_grads_case(batch=32):
        """`correct` has no independent reference yet (PERF.md section 7):
        the whole ViT-B/16 loss gradient at published widths on one seeded
        batch, with the fused kernel (what the registry model compiles to
        here) against the same model with ``dense_core``, and both against
        the fp32 model in true fp32 matmuls. Per parameter tensor:
        max |a - b| / max |b|."""
        from flax.traverse_util import flatten_dict
        from distributed_parameter_server_for_ml_training_tpu.train.steps \
            import cross_entropy_loss

        fused = get_model("vit_b16", num_classes=1000, dtype=jnp.bfloat16,
                          image_size=224)
        dense = fused.clone(attention_fn=at.dense_core)
        exact = get_model("vit_b16", num_classes=1000, dtype=jnp.float32,
                          image_size=224).clone(attention_fn=dense_attention)
        ks = jax.random.split(jax.random.PRNGKey(27), 3)
        images = jax.random.normal(ks[0], (batch, 224, 224, 3), jnp.float32)
        labels = jax.random.randint(ks[1], (batch,), 0, 1000)
        params = jax.jit(partial(fused.init, train=False))(
            ks[2], images[:1])["params"]

        def grads(model):
            return jax.grad(lambda p: cross_entropy_loss(
                model.apply({"params": p}, images, train=True), labels))

        exe, compile_s, calls = compiled(grads(fused), params)
        assert calls == 24, calls
        g_fused = flatten_dict(exe(params), sep="/")
        exe_dense, _s, dense_calls = compiled(grads(dense), params)
        assert dense_calls == 0, dense_calls
        g_dense = flatten_dict(exe_dense(params), sep="/")
        with jax.default_matmul_precision("highest"):
            g_exact = flatten_dict(jax.jit(grads(exact))(params), sep="/")

        def worst(a, b):
            diffs = {k: rel_err(a[k], b[k]) for k in b}
            k = max(diffs, key=diffs.get)
            return {"max": round(diffs[k], 5), "tensor": k,
                    "median": round(float(np.median(list(diffs.values()))),
                                    5)}

        out = {"batch": batch, "tensors": len(g_exact),
               "compile_s": round(compile_s, 2), "mosaic_calls": calls,
               "fused_vs_dense": worst(g_fused, g_dense),
               "fused_vs_fp32": worst(g_fused, g_exact),
               "dense_vs_fp32": worst(g_dense, g_exact)}
        # the kernel keeps its logits in fp32, so it may not be further
        # from the truth than dense_core is by more than rounding
        assert out["fused_vs_fp32"]["max"] <= max(
            0.05, 1.25 * out["dense_vs_fp32"]["max"]), out
        return out

    check("vit_b16_grads_fused_vs_dense")(vit_grads_case)

    def codes_off(got, want) -> int:
        """How many int8 codes differ between a kernel and its reference.
        They may differ by one level where x/scale lands on a rounding
        boundary (two compilers' divides need not round alike); never by
        more."""
        got = np.asarray(got, np.int32)
        want = np.asarray(want, np.int32)
        assert got.shape == want.shape
        diff = np.abs(got - want)
        assert diff.max(initial=0) <= 1, int(diff.max())
        return int(np.count_nonzero(diff))

    def codes_agree(got, want) -> dict:
        off = codes_off(got, want)
        assert off <= 1e-3 * np.size(want), (off, np.size(want))
        return {"codes": int(np.size(want)), "codes_off_by_one": off}

    def quantize_case(shape):
        x = jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)
        info = {"shape": list(shape), "elements": int(x.size)}
        # round-to-nearest vs the jnp formulation
        exe, secs, calls = compiled(
            partial(qz.quantize_int8, stochastic=False, use_pallas=True), x)
        assert calls >= 1
        vals, scales = exe(x)
        rvals, rscales = qz.quantize_int8(x, use_pallas=False)
        np.testing.assert_allclose(np.asarray(scales), np.asarray(rscales),
                                   rtol=1e-6)
        info["rtn"] = {**codes_agree(vals, rvals),
                       "compile_s": round(secs, 2)}
        # dequantize: one multiply per element, so exact
        exe, secs, calls = compiled(
            partial(qz.dequantize_int8, shape=tuple(shape),
                    use_pallas=True), vals, scales)
        assert calls >= 1
        deq = exe(vals, scales)
        rdeq = qz.dequantize_int8(vals, scales, tuple(shape),
                                  use_pallas=False)
        np.testing.assert_array_equal(np.asarray(deq), np.asarray(rdeq))
        info["dequantize"] = {"exact": True, "compile_s": round(secs, 2)}
        # stochastic: no elementwise reference — every value within one
        # level of x, unbiased on average, seeded (same seed repeats,
        # another seed differs), and not simply round-to-nearest
        exe, secs, calls = compiled(
            partial(qz.quantize_int8, stochastic=True, use_pallas=True),
            x, jnp.int32(11))
        assert calls >= 1
        sv, ss = exe(x, jnp.int32(11))
        sv_again, _ = exe(x, jnp.int32(11))
        sv_other, _ = exe(x, jnp.int32(12))
        sdeq = np.asarray(qz.dequantize_int8(sv, ss, tuple(shape),
                                             use_pallas=False))
        xs = np.asarray(x)
        level = float(np.max(np.asarray(ss)))
        assert np.max(np.abs(sdeq - xs)) <= level * 1.0001
        bias = float(np.mean(sdeq - xs)) / level
        assert abs(bias) < 1e-2, bias
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(sv_again))
        differs_seed = float(np.mean(np.asarray(sv) != np.asarray(sv_other)))
        differs_rtn = float(np.mean(np.asarray(sv) != np.asarray(vals)))
        assert differs_seed > 0.05 and differs_rtn > 0.05, \
            (differs_seed, differs_rtn)
        info["stochastic"] = {
            "bias_in_levels": round(bias, 6),
            "fraction_differing_from_rtn": round(differs_rtn, 4),
            "fraction_differing_across_seeds": round(differs_seed, 4),
            "compile_s": round(secs, 2)}
        # wire codec primitive: shared scalar scale, int8 codes
        scale = jnp.float32(float(np.max(np.abs(xs))) / 127.0)
        exe, secs, calls = compiled(
            partial(qz.wire_quantize, levels=127, use_pallas=True),
            x, scale)
        assert calls >= 1
        wq = exe(x, scale)
        rwq = qz.wire_quantize(x, scale, levels=127, use_pallas=False)
        info["wire"] = {**codes_agree(wq, rwq), "compile_s": round(secs, 2)}
        return info

    # ResNet-18's largest conv (512x512x3x3 = 2,359,296 elements; its
    # per-block scales fill the (n_blocks, 1) SMEM array), and a size that
    # is not a multiple of the 128-lane row.
    check("quantize_2359296")(lambda: quantize_case((3, 3, 512, 512)))
    check("quantize_non_multiple_of_128")(
        lambda: quantize_case((1000, 77)))

    def codec_case():
        model = ResNet18(num_classes=100, dtype=jnp.bfloat16)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 32, 32, 3), jnp.float32),
                            train=False)["params"]
        shapes = flatten_params(params, as_numpy=False)
        n_params = sum(int(a.size) for a in shapes.values())
        assert n_params == 11_220_132, n_params
        keys = jax.random.split(jax.random.PRNGKey(3), len(shapes))
        flat = {name: 0.01 * jax.random.normal(kk, a.shape, jnp.float32)
                for kk, (name, a) in zip(keys, shapes.items())}
        plan = tuple((name, "int8") for name in flat)
        big = sum(1 for a in flat.values()
                  if a.size >= qz.PALLAS_WIRE_MIN_SIZE)
        # The encode program by itself: one pallas_call per big tensor.
        totals, amax, topk = dc._phase_stats(dict(flat), {}, plan, (), True)
        scales = {n: np.float32(float(a) / 127.0)
                  for n, a in jax.device_get(amax).items()}
        t0 = time.perf_counter()
        exe = dc._phase_encode.lower(totals, topk, scales, plan, True,
                                     True).compile()
        compile_s = time.perf_counter() - t0
        calls = exe.as_text().count(mosaic)
        assert calls >= big, (calls, big)
        # Through the public surface, against the NumPy reference codec.
        codec = dc.DeviceCodec(error_feedback=True, use_pallas=True)
        t0 = time.perf_counter()
        wire = codec.encode_now(flat)
        first_s = time.perf_counter() - t0
        codec.reset()
        t0 = time.perf_counter()
        wire = codec.encode_now(flat)
        steady_s = time.perf_counter() - t0
        ref = compress_push({n: np.asarray(a) for n, a in flat.items()},
                            ef=ErrorFeedback())
        assert list(wire) == list(ref)
        off = total = 0
        for name, want in ref.items():
            got = np.asarray(wire[name])
            if want.dtype == np.int8:
                off += codes_off(got, want)
                total += want.size
            else:
                np.testing.assert_array_equal(got, want)
        assert off <= 1e-3 * total, (off, total)
        return {"parameters": n_params, "tensors": len(flat),
                "tensors_through_pallas": big, "mosaic_calls": calls,
                "encode_program_compile_s": round(compile_s, 2),
                "first_encode_s": round(first_s, 2),
                "steady_encode_s": round(steady_s, 4),
                "codes": total, "codes_off_by_one_vs_numpy": off}

    check("device_codec_resnet18_tree")(codec_case)

    if failed:
        print(f"kernel checks failed: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child-kernels"]:
        raise SystemExit(_child_kernels(tuple(sys.argv[2:])))
    raise SystemExit(main())
