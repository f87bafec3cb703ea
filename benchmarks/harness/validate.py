"""The benchmark's own validator: the contract's shape of ``BENCHMARK.json``
and of the one result line. ``run.py`` passes every result through
:func:`check_result` before it prints it, and the tests call the same
functions.
"""

from __future__ import annotations

import json
import math
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
MAX_BOUND = 0.1


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def _line(x, limit: int = 200) -> bool:
    return (isinstance(x, str) and 1 <= len(x) <= limit
            and "\n" not in x and "\t" not in x)


def check_result(result, *, owed: dict, trace: bool,
                 all_owed: bool = True) -> list[str]:
    """Problems with one result object; empty when it meets the contract.

    ``owed`` maps each metric this run owes (the cell's end-to-end metrics
    when ``trace`` is false, its per-layer metrics when true) to its unit.
    With ``all_owed`` false a per-layer metric may be absent (a reader that
    found nothing to read), but at least one must be there.
    """
    bad: list[str] = []
    if not isinstance(result, dict):
        return ["the result is not a JSON object"]
    for key in RESULT_KEYS:
        if key not in result:
            bad.append(f"key {key!r} is missing")
    if bad:
        return bad
    if not isinstance(result["correct"], bool):
        bad.append("'correct' is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(result[key], int) or isinstance(result[key], bool) \
                or result[key] < 0:
            bad.append(f"{key!r} is not a count")
    if not bad and result["failed"] > result["attempted"]:
        bad.append("'failed' exceeds 'attempted'")

    metrics = result["metrics"]
    if not isinstance(metrics, dict) or not metrics:
        bad.append("'metrics' is not a non-empty object")
        metrics = {}
    for name, m in metrics.items():
        if not NAME_RE.match(name):
            bad.append(f"metric name {name!r} has a character a name may "
                       f"not have")
        if name not in owed:
            bad.append(f"metric {name!r} is not one this run owes")
        if not isinstance(m, dict) or "value" not in m or "unit" not in m:
            bad.append(f"metric {name!r} lacks value or unit")
            continue
        if not _number(m["value"]):
            bad.append(f"metric {name!r}: value {m['value']!r} is not a "
                       f"finite number")
        if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
            bad.append(f"metric {name!r}: unit {m['unit']!r} has a "
                       f"character a unit may not have")
        elif name in owed and m["unit"] != owed[name]:
            bad.append(f"metric {name!r}: unit {m['unit']!r} is not "
                       f"{owed[name]!r}")
    missing = [n for n in owed if n not in metrics]
    if missing and (all_owed or len(missing) == len(owed)):
        bad.append(f"metrics owed and absent: {sorted(missing)}")

    device = result["device"]
    if not isinstance(device, dict):
        return bad + ["'device' is not an object"]
    for key in ("platform", "kind"):
        if not _line(device.get(key)):
            bad.append(f"device.{key} is missing or not a one-line string")
    if not isinstance(device.get("count"), int) or device["count"] < 1:
        bad.append("device.count is not a positive whole number")
    if not _number(device.get("memory_peak_bytes")) \
            or device["memory_peak_bytes"] <= 0:
        bad.append("device.memory_peak_bytes is not a positive number")
    if trace:
        window, busy = device.get("window_s"), device.get("busy_s")
        if not _number(window) or not _number(busy):
            bad.append("a traced run's device needs window_s and busy_s")
        elif not 0 < busy <= window:
            bad.append(f"busy_s {busy!r} is not above 0 and at most "
                       f"window_s {window!r}")
    if "breakdown" in result:
        bad += _check_breakdown(result["breakdown"])
    return bad


def _check_breakdown(breakdown) -> list[str]:
    if not isinstance(breakdown, dict):
        return ["'breakdown' is not an object"]
    bad = []
    for key in ("device_ops", "idle_gaps"):
        rows = breakdown.get(key)
        if not isinstance(rows, list) or len(rows) > 10:
            bad.append(f"breakdown.{key} is not a list of at most 10")
            continue
        for row in rows:
            if not (isinstance(row, list) and len(row) == 2
                    and isinstance(row[0], str) and _number(row[1])):
                bad.append(f"breakdown.{key} entry {row!r} is not "
                           f"[name, seconds]")
    return bad


def check_last_line(stdout_text: str, **kw) -> list[str]:
    """Problems with a run's standard output: its last line must be the
    result object and nothing may follow it."""
    lines = stdout_text.split("\n")
    if not stdout_text.endswith("\n") or len(lines) < 2:
        return ["standard output does not end with a complete line"]
    try:
        result = json.loads(lines[-2])
    except ValueError as e:
        return [f"the last line is not JSON: {e}"]
    return check_result(result, **kw)


def _exact_keys(entry, required, optional=()) -> list[str]:
    if not isinstance(entry, dict):
        return [f"{entry!r} is not an object"]
    keys = set(entry)
    bad = [f"{entry.get('name', entry)!r} lacks key {k!r}"
           for k in required if k not in keys]
    bad += [f"{entry.get('name', entry)!r} has a key the contract does not "
            f"know: {k!r}" for k in sorted(keys - set(required)
                                           - set(optional))]
    return bad


def check_benchmark(bench) -> list[str]:
    """Problems with a ``BENCHMARK.json`` object against the contract's
    static rules (keys, names, units, references between entries)."""
    bad = _exact_keys(bench, ("command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"))
    if bad:
        return bad
    if len(json.dumps(bench)) > 64 * 1024:
        bad.append("the file is over 64 KiB")
    cmd = bench["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_line(w) for w in cmd)):
        bad.append("command is not a list of 1 to 32 one-line strings")
    paths = bench["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(isinstance(p, str) and PATH_RE.match(p)
                    and not p.startswith("/") and ".." not in p.split("/")
                    for p in paths)):
        bad.append("paths is not 1 to 16 relative directories")
        paths = []
    if not (isinstance(bench["run_seconds"], int)
            and 1 <= bench["run_seconds"] <= 51):
        bad.append("run_seconds is not a whole number from 1 to 51")

    def under_paths(f):
        return any(f.startswith(p.rstrip("/") + "/") for p in paths)

    def unique(entries, what):
        names = [e.get("name") for e in entries if isinstance(e, dict)]
        for n in names:
            if not isinstance(n, str) or not NAME_RE.match(n):
                bad.append(f"{what} name {n!r} is not a name")
        if len(set(names)) != len(names):
            bad.append(f"two {what}s share a name")
        return set(names)

    configs = bench["configs"]
    if not (isinstance(configs, list) and 1 <= len(configs) <= 24):
        return bad + ["configs is not a list of 1 to 24"]
    for c in configs:
        bad += _exact_keys(c, ("name", "source", "file", "reduced", "why"))
    config_names = unique(configs, "configuration")
    files = [c.get("file") for c in configs]
    if len(set(files)) != len(files):
        bad.append("two configurations share a file")
    for c in configs:
        if not (isinstance(c.get("file"), str) and PATH_RE.match(c["file"])
                and under_paths(c["file"])):
            bad.append(f"configuration {c.get('name')!r}: file is not "
                       f"under paths")
        if not _line(c.get("source")) or not _line(c.get("why")):
            bad.append(f"configuration {c.get('name')!r}: source and why "
                       f"are one line of at most 200 characters")
        red = c.get("reduced")
        if not (isinstance(red, list) and len(red) <= 16
                and all(isinstance(k, str) and NAME_RE.match(k)
                        for k in red)):
            bad.append(f"configuration {c.get('name')!r}: reduced is not a "
                       f"list of at most 16 names")

    cells = bench["workloads"]
    if not (isinstance(cells, list) and 1 <= len(cells) <= 24):
        return bad + ["workloads is not a list of 1 to 24"]
    for w in cells:
        bad += _exact_keys(w, ("name", "config", "traffic", "chips", "why"))
    cell_names = unique(cells, "workload")
    pairs = [(w.get("config"), w.get("traffic")) for w in cells]
    if len(set(pairs)) != len(pairs):
        bad.append("a pair of configuration and traffic appears twice")
    for w in cells:
        if w.get("config") not in config_names:
            bad.append(f"workload {w.get('name')!r} names no configuration")
        if not (isinstance(w.get("traffic"), str)
                and NAME_RE.match(w["traffic"])):
            bad.append(f"workload {w.get('name')!r}: traffic is not a name")
        if w.get("chips") not in (1, 4):
            bad.append(f"workload {w.get('name')!r}: chips is not 1 or 4")
        if not _line(w.get("why")):
            bad.append(f"workload {w.get('name')!r}: why is not one line "
                       f"of at most 200 characters")
    used = {w.get("config") for w in cells}
    for n in config_names - used:
        bad.append(f"configuration {n!r} is used by no workload")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} workloads ask for 4 chips")

    e2e, layer = bench["end_to_end"], bench["per_layer"]
    if not (isinstance(e2e, list) and 1 <= len(e2e) <= 16):
        return bad + ["end_to_end is not a list of 1 to 16"]
    if not (isinstance(layer, list) and 1 <= len(layer) <= 128):
        return bad + ["per_layer is not a list of 1 to 128"]
    for m in e2e:
        bad += _exact_keys(m, ("name", "unit", "better", "bound", "source"),
                           ("workloads",))
        if m.get("source") not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end metric {m.get('name')!r} takes only "
                       f"host_clock or device_trace")
        b = m.get("bound")
        if not _number(b) or not 0 < b <= MAX_BOUND:
            bad.append(f"metric {m.get('name')!r}: bound is not in "
                       f"(0, {MAX_BOUND}]")
    for m in layer:
        bad += _exact_keys(m, ("name", "unit", "better", "source", "layer",
                               "moves"), ("workloads",))
        if not _line(m.get("layer")):
            bad.append(f"metric {m.get('name')!r}: layer is not one line")
    unique(list(e2e) + list(layer), "metric")
    e2e_by_name = {m.get("name"): m for m in e2e}
    if "setup_s" not in e2e_by_name:
        bad.append("no end-to-end metric is setup_s")

    def cells_of(m):
        return set(m.get("workloads") or cell_names)

    for m in list(e2e) + list(layer):
        if not (isinstance(m.get("unit"), str) and UNIT_RE.match(m["unit"])):
            bad.append(f"metric {m.get('name')!r}: unit {m.get('unit')!r} "
                       f"is not a unit")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m.get('name')!r}: better is not lower or "
                       f"higher")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m.get('name')!r}: unknown source")
        for w in m.get("workloads") or ():
            if w not in cell_names:
                bad.append(f"metric {m.get('name')!r} lists workload "
                           f"{w!r}, which does not exist")
    for m in layer:
        target = e2e_by_name.get(m.get("moves"))
        if target is None:
            bad.append(f"metric {m.get('name')!r} moves no end-to-end "
                       f"metric")
        elif not cells_of(m) <= cells_of(target):
            bad.append(f"metric {m.get('name')!r} is reported in a cell "
                       f"where {m['moves']!r} is not")
    for w in cell_names:
        e = [m for m in e2e if w in cells_of(m)]
        if not any(m.get("name") == "setup_s" for m in e) or len(e) < 2:
            bad.append(f"workload {w!r} lacks setup_s and one other "
                       f"end-to-end metric")
        if not any(w in cells_of(m) for m in layer):
            bad.append(f"workload {w!r} has no per-layer metric")
    return bad
