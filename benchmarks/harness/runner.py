"""One run of one cell: set-up, a window between two device-complete edges
that holds whole epochs, the checks that decide ``correct``, and the one
result line."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback
from dataclasses import dataclass

from . import spec
from .compiles import CompileLog
from .device import NoAccelerator, describe, peak_bytes, require_devices
from .guard import StdoutGuard
from .validate import check_result

#: a run's set-up may take this long before it is given up (the contract
#: allows a first, compiling run 1200 s in all)
WARM_LIMIT_S = 1000.0
#: the window closes at the first epoch end after ``--seconds``: an epoch
#: that has not ended this long after them never will
LAST_EDGE_LIMIT_S = 120.0


@dataclass
class RunContext:
    """What a driver is given to start its session."""
    cell: spec.Cell
    seed: int
    devices: list
    log_path: str


@dataclass
class RunRecord:
    """What the metric readers are given: the run as measured."""
    cell: spec.Cell
    chips: int
    setup_s: float
    edges: tuple                 # (first, last): dicts from Session.edge()
    memory_peak_bytes: int
    flops_per_image: float
    images_per_device_step: int  # images one run of the step module takes
    peak: dict                   # the device kind's file under peaks/
    compile_s_in_setup: float
    compiles_in_window: int
    cache_misses: int
    trace: object = None         # xplane.TraceReduction in a traced run

    @property
    def window_s(self) -> float:
        return self.edges[1]["t"] - self.edges[0]["t"]

    def delta(self, key: str) -> float:
        return self.edges[1][key] - self.edges[0][key]

    @property
    def images_per_s_per_chip(self) -> float:
        return self.delta("images") / self.window_s / self.chips


def _sleep_until(t: float) -> None:
    time.sleep(max(0.0, t - time.monotonic()))


def _profile_options():
    """The device's trace alone. Python-level tracing would not fit, and
    the host tracer, at any level that records the runtime's annotations,
    starves the threads that feed the device: with it on, a 4 s slice held
    stalls of 1 to 6 s that no untraced window has, and stopping the
    profiler took 20 to 65 s instead of 2 (my chip runs, PR 24)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    return options


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, out_dir: str, log_path: str,
             allow_cpu: bool = False) -> dict:
    sys.path.insert(0, os.path.dirname(cell.bench_dir))  # the program
    from distributed_parameter_server_for_ml_training_tpu.utils \
        .compile_cache import enable_compile_cache
    import jax
    # The trainers initialise their weights op by op, dozens of programs
    # that each compile in under JAX's one-second threshold for the cache
    # and would compile again in every run: cache them too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileLog(enable_compile_cache())
    compiles.install()
    devices = require_devices(cell.chips, allow_cpu)
    info = describe()
    peak = cell.peak(info["kind"])
    t_devices = time.monotonic()

    session = cell.driver().start(RunContext(
        cell=cell, seed=seed, devices=devices, log_path=log_path))
    t_started = time.monotonic()
    first = session.edge(0.0, t_start + WARM_LIMIT_S)
    print(f"[bench] window opens: set-up {first['t'] - t_start:.3f} s "
          f"(imports and reaching the chip {t_devices - t_start:.1f}, data "
          f"and trainer {t_started - t_devices:.1f}, warm-up epoch "
          f"{first['t'] - t_started:.1f}), edge {first}", flush=True)

    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        # The traced slice is the END of the window: its last
        # ``trace_slice_s`` seconds and what the last epoch needs beyond
        # ``--seconds``. The last edge is taken before the profiler is
        # stopped: stopping takes seconds of host work that no window
        # should hold.
        slice_s = min(float(cell.traffic.get("trace_slice_s", 4.0)),
                      seconds / 2.0)
        shutil.rmtree(trace_dir, ignore_errors=True)
        _sleep_until(first["t"] + seconds - slice_s)
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=_profile_options())
    last = session.edge(first["t"] + seconds,
                        first["t"] + seconds + LAST_EDGE_LIMIT_S)
    if trace:
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        print(f"[bench] stop_trace took {time.monotonic() - t_stop:.1f} s",
              flush=True)
    memory = peak_bytes(devices, allow_cpu)
    print(f"[bench] window closes after {last['t'] - first['t']:.3f} s, "
          f"edge {last}", flush=True)

    run = RunRecord(
        cell=cell, chips=cell.chips,
        setup_s=first["t"] - t_start, edges=(first, last),
        memory_peak_bytes=memory,
        flops_per_image=cell.train_flops_per_image(),
        images_per_device_step=session.images_per_device_step, peak=peak,
        compile_s_in_setup=compiles.compile_seconds_before(first["t"]),
        compiles_in_window=compiles.backend_compiles_between(
            first["t"], last["t"]),
        cache_misses=len(compiles.new_cache_entries()))
    print(f"[bench] in the window: {run.compiles_in_window} backend "
          f"compiles, {compiles.traces_between(first['t'], last['t'])} "
          f"traces or lowerings; new compile-cache entries "
          f"{compiles.new_cache_entries()}", flush=True)

    checks, failed = session.finish(first, last)
    checks = {k: bool(v) for k, v in checks.items()}
    checks["no_compile_in_window"] = run.compiles_in_window == 0
    checks["work_was_done"] = run.delta("images") > 0 and run.window_s > 0

    device = dict(info, memory_peak_bytes=memory)
    result = {"correct": all(checks.values()),
              "attempted": int(run.delta("attempted")),
              "failed": int(failed),
              "metrics": {}, "device": device,
              "checks": checks, "workload": cell.name, "seed": seed}
    if trace:
        from . import xplane
        planes = xplane.load_planes(xplane.find_xplane(trace_dir))
        run.trace = xplane.reduce_planes(planes)
        device["window_s"] = run.trace.window_s
        device["busy_s"] = run.trace.busy_s
        result["breakdown"] = run.trace.breakdown()
        with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
            json.dump(xplane.summary(planes, run.trace), f, indent=1)
        shutil.rmtree(trace_dir, ignore_errors=True)  # tens of MB
        owed, kind = cell.per_layer, "layer_metrics"
    else:
        owed, kind = cell.end_to_end, "end_to_end"
    for name, unit in owed.items():
        value = spec.load_module(kind, name, cell.bench_dir).read(run)
        if value is None:
            print(f"[bench] {name}: the reader found nothing to read",
                  file=sys.stderr, flush=True)
            continue
        result["metrics"][name] = {"value": float(value), "unit": unit}
    print(f"[bench] result {json.dumps(result)}", flush=True)
    return result


def main(argv, *, t_start: float | None = None, allow_cpu: bool = False,
         root: str = spec.ROOT) -> None:
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    trace = bool(args.trace)

    out_dir = os.path.join(
        root, "chiprun_out", "benchmarks",
        f"{args.workload}.seed{args.seed}.trace{args.trace}")
    log_path = os.path.join(out_dir, "run.log")
    guard = StdoutGuard(log_path)
    try:
        cell = spec.load_cell(args.workload, root)
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=trace, t_start=t_start, out_dir=out_dir,
                          log_path=log_path, allow_cpu=allow_cpu)
        problems = check_result(
            result, owed=cell.per_layer if trace else cell.end_to_end,
            trace=trace, all_owed=not trace)
    except NoAccelerator as e:
        guard.fail(str(e), 3)
    except BaseException:  # noqa: BLE001 — the boundary: report, exit non-zero
        traceback.print_exc()
        guard.fail("the run failed; no result is printed", 1)
    if problems:
        guard.fail("the result does not meet the contract: "
                   + "; ".join(problems), 2)
    guard.emit_and_exit(result)
