"""Profiler capture + achieved-FLOPs accounting for the hot loop.

``--profile-dir`` has always dumped raw ``jax.profiler`` traces that
nobody parsed; this module is the write half of the perf observatory
(the read half is :mod:`..analysis.device_profile`):

- :func:`capture` — the capture bracket, the package's one wrapper of
  ``jax.profiler.start_trace`` / ``stop_trace`` (``--profile-dir``, the
  profile trigger engine, the experiments), plus dump discovery.
- :func:`compiled_cost` — ``lowered.compile().cost_analysis()`` flops +
  bytes for ONE compiled step. Always compile the SINGLE step for this
  (not a scanned window): XLA reports the whole program, and a
  80-step scan would over-state per-step flops by 80x.
- :func:`mfu` — achieved / peak FLOPs. Peak comes from
  :data:`PEAK_FLOPS_BY_KIND` keyed on ``jax.devices()[0].device_kind``;
  an unknown kind yields ``None`` rather than an invented number — an
  MFU against a guessed peak is worse than no MFU.
"""

from __future__ import annotations

import contextlib
import glob
import os

__all__ = [
    "PEAK_FLOPS_BY_KIND",
    "capture",
    "compiled_cost",
    "find_profile_dumps",
    "mfu",
    "peak_flops",
    "require_peak_flops",
    "prune_capture",
]

#: device_kind -> peak dense-matmul FLOP/s at the precision the training
#: step actually runs (bf16 on TPU, fp32 on CPU-like hosts has no
#: meaningful peak so CPU kinds are deliberately absent). Sources: cloud
#: TPU spec sheets (v4 275 TF bf16; v5e 197 TF bf16; v5p 459 TF bf16).
PEAK_FLOPS_BY_KIND = {
    "TPU v4": 275.0e12,
    "TPU v5 lite": 197.0e12,
    "TPU v5e": 197.0e12,
    "TPU v5p": 459.0e12,
}


@contextlib.contextmanager
def capture(logdir: str):
    """Profiler capture bracket: ``with capture(dir): hot_loop()``.

    Creates ``logdir`` and brackets the body with
    ``jax.profiler.start_trace``/``stop_trace``; the dump lands under
    ``logdir/plugins/profile/<timestamp>/`` (one xplane.pb + one
    Chrome-format ``*.trace.json.gz`` per host). End the body with
    ``jax.block_until_ready`` so the traced work is inside it."""
    import jax
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_profile_dumps(logdir: str) -> list[str]:
    """Chrome-trace files under a capture dir, newest run first.

    Accepts the capture root (scans ``plugins/profile/*/``), a specific
    run dir, or a direct path to one trace file."""
    if os.path.isfile(logdir):
        return [logdir]
    found: list[str] = []
    for pat in ("*.trace.json.gz", "*.trace.json"):
        found += glob.glob(os.path.join(
            logdir, "plugins", "profile", "*", pat))
        found += glob.glob(os.path.join(logdir, pat))
    # Newest capture first: the run timestamp is the parent dir name.
    return sorted(set(found), key=lambda p: (os.path.dirname(p), p),
                  reverse=True)


def prune_capture(logdir: str) -> list[str]:
    """Delete the raw profiler dump under a capture dir once attribution
    has JOINED it into an artifact; returns the paths removed.

    The capture dirs are big (one xplane.pb + one multi-MB Chrome trace
    per host per capture) and, before this, only the codec-profile
    experiment cleaned up after itself — every other capture path
    (``cli perf profile``, bench, the trigger engine) left them on disk
    forever. Callers prune ONLY after a successful attribution: a
    failed parse keeps the raw dump as the evidence. Removes the whole
    ``plugins/`` capture tree plus any direct ``*.trace.json[.gz]``
    files; never raises (a half-pruned dir degrades to stray files, not
    a failed capture)."""
    import shutil

    removed: list[str] = []
    if os.path.isfile(logdir):
        try:
            os.remove(logdir)
            return [logdir]
        except OSError:
            return []
    plugins = os.path.join(logdir, "plugins")
    if os.path.isdir(plugins):
        shutil.rmtree(plugins, ignore_errors=True)
        if not os.path.exists(plugins):
            removed.append(plugins)
    for pat in ("*.trace.json.gz", "*.trace.json", "*.xplane.pb"):
        for path in glob.glob(os.path.join(logdir, pat)):
            try:
                os.remove(path)
                removed.append(path)
            except OSError:
                pass
    return removed


def _as_cost_dict(cost) -> dict:
    """``cost_analysis()`` returns a dict on current jax, a list of one
    dict on older releases, and None on backends that don't implement
    it; normalize to a (possibly empty) dict."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return cost if isinstance(cost, dict) else {}


def compiled_cost(compiled) -> dict:
    """``{"flops": float|None, "bytes_accessed": float|None}`` from a
    ``Compiled`` object (``jax.jit(f).lower(*args).compile()``). Never
    raises: backends without cost analysis report None values."""
    try:
        cost = _as_cost_dict(compiled.cost_analysis())
    except Exception:  # noqa: BLE001 — backend-optional surface
        cost = {}
    flops = cost.get("flops")
    by = cost.get("bytes accessed", cost.get("bytes_accessed"))
    return {
        "flops": float(flops) if isinstance(flops, (int, float)) else None,
        "bytes_accessed": float(by) if isinstance(by, (int, float))
        else None,
    }


def peak_flops(device_kind: str) -> float | None:
    """Peak FLOP/s for a device kind, or None when unknown (CPU, new
    hardware this table hasn't met) — callers degrade to mfu=None."""
    return PEAK_FLOPS_BY_KIND.get(str(device_kind))


def require_peak_flops(device_kind: str) -> float:
    """:func:`peak_flops` for callers whose whole output is a utilisation
    (the ``experiments/`` MFU scripts): a device that is not in the table
    is an error, never a default — an MFU against a guessed peak is worse
    than none."""
    peak = peak_flops(device_kind)
    if peak is None:
        raise LookupError(
            f"no peak FLOP/s recorded for device kind {device_kind!r} "
            f"(PEAK_FLOPS_BY_KIND); refusing to report a utilisation")
    return peak


def mfu(flops_per_step: float | None, steps_per_s: float | None,
        device_kind: str, n_devices: int = 1) -> float | None:
    """Model FLOPs utilization: achieved FLOP/s over peak. None when any
    input is unavailable (no cost analysis, unknown device kind, no
    measured rate) — never a made-up number."""
    peak = peak_flops(device_kind)
    if not peak or not flops_per_step or not steps_per_s:
        return None
    if n_devices < 1:
        return None
    return (flops_per_step * steps_per_s) / (peak * n_devices)
