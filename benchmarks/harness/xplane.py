"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData``. What a trace of this installation
holds (looked at by hand, TPU v5e, jax 0.9.0): one plane ``/device:TPU:<n>``
for each chip with the lines ``Steps``, ``XLA Modules`` (one event for each
run of a jitted program, named ``jit_<function>(<fingerprint>)``), ``XLA
Ops`` (the serial line: one event for each HLO instruction as the core
executes them, named by the instruction's whole HLO text) and ``Async XLA
Ops`` (spans of asynchronous copies and collectives, which overlap the
serial line). With the host tracer on there is also ``/host:CPU`` with a
line for each host thread; the benchmark traces the device alone
(``runner._profile_options`` says why), so nothing here reads it. All times
are nanoseconds on the trace's own clock.

The rules that make ``0 < busy_s <= window_s`` hold by construction:

- one line per device plane is read for busy time, the serial op line;
- ``window_s`` is taken on that plane's own clock, from the start of the
  first to the end of the last run of the train-step module (the module that
  takes most of the device's time), so it is trimmed to whole steps;
- ``busy_s`` is the length of the *union* of the op intervals clipped to that
  window: overlapping or nested events count once, and nothing outside the
  window counts at all;
- several chips report the mean over devices, never the sum.

Everything below :func:`load_planes` works on plain tuples, so the tests feed
it synthetic interval lists as well as a recorded trace.
"""

from __future__ import annotations

import re
import statistics
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)?$")
#: idle gaps shorter than this are the core's own bubbles between
#: instructions, not the host's doing
SHORT_GAP_NS = 20_000

Event = tuple  # (name, start_ns, end_ns)


class TraceError(RuntimeError):
    pass


@dataclass
class Plane:
    name: str
    lines: dict = field(default_factory=dict)   # line name -> [Event]


def load_planes(path: str) -> list[Plane]:
    """The device planes of a trace, as plain tuples."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        out = Plane(plane.name)
        for line in plane.lines:
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))
                      for e in line.events]
            if events:
                out.lines[line.name] = events
        planes.append(out)
    return planes


# -- interval arithmetic ------------------------------------------------------

def union(intervals, lo: float | None = None,
          hi: float | None = None) -> list[tuple[float, float]]:
    """Sorted disjoint intervals covering the same points as ``intervals``
    ((start, end) pairs, in any order, overlapping or nested), clipped to
    [lo, hi]."""
    clipped = []
    for start, end in intervals:
        if lo is not None:
            start = max(start, lo)
        if hi is not None:
            end = min(end, hi)
        if end > start:
            clipped.append((start, end))
    clipped.sort()
    out: list[tuple[float, float]] = []
    for start, end in clipped:
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals) -> float:
    return sum(end - start for start, end in intervals)


def gaps(covered, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that the disjoint sorted ``covered`` leaves."""
    out, at = [], lo
    for start, end in covered:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events) -> dict:
    """Seconds by name, each event charged its duration less the events
    nested inside it on the same line (a ``while`` is not charged its
    body)."""
    out: dict = {}
    stack: list[list] = []   # [name, end, child_ns, start]

    def close(item):
        name, end, child, start = item
        out[name] = out.get(name, 0.0) + max(0.0, (end - start) - child)
        if stack:
            stack[-1][2] += end - start

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        stack.append([name, end, 0.0, start])
    while stack:
        close(stack.pop())
    return {k: v / 1e9 for k, v in out.items()}


# -- names ---------------------------------------------------------------------

def op_name(hlo_text: str) -> str:
    """``%fusion.5 = (...) fusion(...), kind=...`` -> ``fusion.5``."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


def op_kind(hlo_text: str) -> str:
    """``%convolution_add_fusion.7 = ...`` -> ``convolution_add_fusion``:
    the instruction's name without its number, so that the twelve layers'
    copies of one fusion add up."""
    return re.sub(r"\.\d+$", "", op_name(hlo_text))


def module_name(event_name: str) -> str:
    """``jit_worker_step(4528856758037216061)`` -> ``jit_worker_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


# -- the reduction -------------------------------------------------------------

@dataclass
class DeviceReduction:
    index: int
    window: tuple[float, float]          # ns, the trace's clock
    busy_ns: float
    steps: list[Event]                   # runs of the step module
    modules: list[Event]                 # runs of every module
    ops: list[Event]                     # serial op line, clipped names kept
    covered: list[tuple[float, float]]   # union of ops within the window


@dataclass
class TraceReduction:
    step_module: str
    devices: list[DeviceReduction]

    @property
    def window_s(self) -> float:
        return statistics.fmean(d.window[1] - d.window[0]
                                for d in self.devices) / 1e9

    @property
    def busy_s(self) -> float:
        return statistics.fmean(d.busy_ns for d in self.devices) / 1e9

    def step_device_ms(self) -> float:
        return statistics.median(
            (e[2] - e[1]) for d in self.devices for e in d.steps) / 1e6

    def step_starts_ms(self) -> list[float]:
        return [e[1] / 1e6 for e in self.devices[0].steps]

    def breakdown(self) -> dict:
        d = self.devices[0]
        lo, hi = d.window
        ops = self_times((op_kind(n), max(s, lo), min(e, hi))
                         for (n, s, e) in d.ops if e > lo and s < hi)
        idle: dict = {}
        for start, end in gaps(d.covered, lo, hi):
            name = attribute_gap(start, end, d.modules)
            idle[name] = idle.get(name, 0.0) + (end - start) / 1e9

        def top(table):
            rows = sorted(table.items(), key=lambda kv: -kv[1])[:10]
            return [[name, seconds] for name, seconds in rows]

        return {"device_ops": top(ops), "idle_gaps": top(idle)}


def attribute_gap(start: float, end: float, modules) -> str:
    """A name for the idle gap [start, end]: the program the device ran
    next, since the gap is the time the host took to hand it over."""
    if end - start < SHORT_GAP_NS:
        return "between_ops"
    after = [m for m in modules if m[2] > end]   # runs that end after it
    if after:
        return f"before:{module_name(min(after, key=lambda m: m[1])[0])}"
    return "unattributed"


def dominant_module(modules_by_device) -> str:
    seconds: dict = {}
    for events in modules_by_device:
        for name, start, end in events:
            key = module_name(name)
            seconds[key] = seconds.get(key, 0.0) + (end - start)
    if not seconds:
        raise TraceError("the device planes hold no module events")
    return max(seconds, key=seconds.get)


def reduce_planes(planes: list[Plane],
                  step_module: str | None = None) -> TraceReduction:
    device_planes = sorted(
        ((int(DEVICE_PLANE.match(p.name).group(1)), p)
         for p in planes if DEVICE_PLANE.match(p.name)),
        key=lambda ip: ip[0])
    device_planes = [(i, p) for i, p in device_planes
                     if p.lines.get(OPS_LINE)]
    if not device_planes:
        raise TraceError(
            f"the trace has no device plane with events on {OPS_LINE!r}; "
            f"planes: {[p.name for p in planes]}")
    step_module = step_module or dominant_module(
        p.lines.get(MODULES_LINE, []) for _i, p in device_planes)
    devices = []
    for index, plane in device_planes:
        steps = sorted((e for e in plane.lines.get(MODULES_LINE, [])
                        if module_name(e[0]) == step_module),
                       key=lambda e: e[1])
        if len(steps) < 2:
            raise TraceError(f"{plane.name} holds {len(steps)} runs of "
                             f"{step_module}; a window needs two")
        window = (steps[0][1], steps[-1][2])
        ops = plane.lines[OPS_LINE]
        covered = union(((s, e) for (_n, s, e) in ops), *window)
        busy = total(covered)
        if not 0 < busy <= window[1] - window[0]:
            raise TraceError(f"{plane.name}: busy {busy} ns is not within "
                             f"the window {window}")
        devices.append(DeviceReduction(
            index, window, busy, steps, plane.lines.get(MODULES_LINE, []),
            ops, covered))
    return TraceReduction(step_module, devices)


def find_xplane(trace_dir: str) -> str:
    import glob
    import os
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise TraceError(f"the profiler wrote no .xplane.pb under "
                         f"{trace_dir}")
    return paths[-1]


def summary(planes: list[Plane], reduction: TraceReduction) -> dict:
    """What a person looking at the trace by hand would note down: written
    beside the log of every traced run."""
    d = reduction.devices[0]
    by_name: dict = {}
    for n, s, e in d.ops:
        by_name[op_name(n)] = by_name.get(op_name(n), 0.0) + (e - s)
    modules: dict = {}
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            for n, s, e in p.lines.get(MODULES_LINE, []):
                key = f"{p.name} {module_name(n)}"
                modules[key] = modules.get(key, 0) + 1
    return {
        "planes": {p.name: {ln: len(ev) for ln, ev in p.lines.items()}
                   for p in planes},
        "step_module": reduction.step_module,
        "module_runs": modules,
        "steps_in_window": [len(x.steps) for x in reduction.devices],
        "window_s": [(x.window[1] - x.window[0]) / 1e9
                     for x in reduction.devices],
        "busy_s": [x.busy_ns / 1e9 for x in reduction.devices],
        "collective_ops": sorted(
            (n for n in by_name if COLLECTIVE.match(n)))[:40],
        # a looser net, to see by eye what the pattern above may miss
        "collective_like_ops": sorted(
            (n for n in by_name if re.search(
                r"all-|reduce-scatter|permute|send|recv", n)
             and not COLLECTIVE.match(n)))[:40],
        "top_ops_inclusive_ms": sorted(
            ((n, ns / 1e6) for n, ns in by_name.items()),
            key=lambda kv: -kv[1])[:30],
        "longest_gaps": [
            {"at_s": (a - d.window[0]) / 1e9, "seconds": (b - a) / 1e9,
             "name": attribute_gap(a, b, d.modules)}
            for a, b in sorted(gaps(d.covered, *d.window),
                               key=lambda g: g[0] - g[1])[:12]],
        "step_periods_ms": [round(b - a, 3) for a, b in zip(
            reduction.step_starts_ms(), reduction.step_starts_ms()[1:])],
    }
