"""The validator: the result line and BENCHMARK.json against the contract."""

import copy
import json
import os

import pytest

from harness import spec, validate

REPO = spec.ROOT

E2E = {"images_per_s_per_chip": "images/s/chip", "setup_s": "s"}
LAYER = {"device.idle_share": "fraction", "step.device_ms": "ms"}


def good(trace: bool = False) -> dict:
    owed = LAYER if trace else E2E
    result = {
        "correct": True, "attempted": 400, "failed": 0,
        "metrics": {n: {"value": 1.5, "unit": u} for n, u in owed.items()},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 13958643712}}
    if trace:
        result["device"].update(window_s=4.0, busy_s=3.9)
        result["breakdown"] = {"device_ops": [["fusion", 1.2]],
                               "idle_gaps": [["unattributed", 0.1]]}
    return result


@pytest.mark.parametrize("trace", [False, True])
def test_a_good_line_is_accepted(trace):
    owed = LAYER if trace else E2E
    assert validate.check_result(good(trace), owed=owed, trace=trace) == []
    text = "noise\n" + json.dumps(good(trace)) + "\n"
    assert validate.check_last_line(text, owed=owed, trace=trace) == []


def _drop(key):
    def f(r):
        del r[key]
    return f


def _set(path, value):
    def f(r):
        for k in path[:-1]:
            r = r[k]
        r[path[-1]] = value
    return f


def _rename_metric(old, new):
    def f(r):
        r["metrics"][new] = r["metrics"].pop(old)
    return f


MALFORMED = {
    "missing correct": (False, _drop("correct")),
    "missing attempted": (False, _drop("attempted")),
    "missing failed": (False, _drop("failed")),
    "missing metrics": (False, _drop("metrics")),
    "missing device": (False, _drop("device")),
    "correct not a bool": (False, _set(["correct"], "yes")),
    "failed above attempted": (False, _set(["failed"], 401)),
    "attempted not a count": (False, _set(["attempted"], 1.5)),
    "metric without unit": (False, _set(["metrics", "setup_s"],
                                        {"value": 1.0})),
    "metric without value": (False, _set(["metrics", "setup_s"],
                                         {"unit": "s"})),
    "metric value NaN": (False, _set(["metrics", "setup_s", "value"],
                                     float("nan"))),
    "metric value a string": (False, _set(["metrics", "setup_s", "value"],
                                          "1.0")),
    "metric owed and absent": (False, lambda r: r["metrics"].pop("setup_s")),
    "metric not owed": (False, _set(["metrics", "extra"],
                                    {"value": 1.0, "unit": "s"})),
    "space in a unit": (False, _set(["metrics", "setup_s", "unit"],
                                    "s per run")),
    "greek letter in a unit": (False, _set(["metrics", "setup_s", "unit"],
                                           "μs")),
    "unit differs from the benchmark's": (
        False, _set(["metrics", "setup_s", "unit"], "ms")),
    "slash in a name": (False, _rename_metric("setup_s", "setup/s")),
    "space in a name": (False, _rename_metric("setup_s", "setup s")),
    "no device kind": (False, lambda r: r["device"].pop("kind")),
    "device count 0": (False, _set(["device", "count"], 0)),
    "no memory peak": (False, lambda r: r["device"].pop("memory_peak_bytes")),
    "memory peak 0": (False, _set(["device", "memory_peak_bytes"], 0)),
    "traced without window_s": (True, lambda r: r["device"].pop("window_s")),
    "traced without busy_s": (True, lambda r: r["device"].pop("busy_s")),
    "busy_s 0": (True, _set(["device", "busy_s"], 0.0)),
    "busy_s above window_s": (True, _set(["device", "busy_s"], 4.0001)),
    "breakdown with 11 ops": (True, _set(
        ["breakdown", "device_ops"], [["op", 1.0]] * 11)),
    "breakdown entry not a pair": (True, _set(
        ["breakdown", "idle_gaps"], [["op"]])),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_each_malformed_line_is_rejected(case):
    trace, damage = MALFORMED[case]
    result = good(trace)
    damage(result)
    owed = LAYER if trace else E2E
    assert validate.check_result(result, owed=owed, trace=trace), case


@pytest.mark.parametrize("text", [
    "", "{}", json.dumps(good()),                     # no newline at the end
    json.dumps(good()) + "\nEPOCH_DONE worker=0\n",  # a line follows it
    "not json\n"])
def test_output_that_does_not_end_on_the_result_is_rejected(text):
    assert validate.check_last_line(text, owed=E2E, trace=False)


def test_a_traced_run_may_lack_a_metric_whose_reader_found_nothing():
    result = good(True)
    del result["metrics"]["step.device_ms"]
    assert validate.check_result(result, owed=LAYER, trace=True,
                                 all_owed=False) == []
    assert validate.check_result(result, owed=LAYER, trace=True)
    result["metrics"].clear()
    assert validate.check_result(result, owed=LAYER, trace=True,
                                 all_owed=False)


def bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_repos_benchmark_json_meets_the_contract():
    assert validate.check_benchmark(bench()) == []


def _metric(b, name):
    return next(m for m in b["end_to_end"] + b["per_layer"]
                if m["name"] == name)


BAD_BENCHMARKS = {
    "unknown top-level key": lambda b: b.update(notes="x"),
    "run_seconds 52": lambda b: b.update(run_seconds=52),
    "absolute path": lambda b: b.update(paths=["/benchmarks"]),
    "path through ..": lambda b: b.update(paths=["../benchmarks"]),
    "bound above a tenth": lambda b: _metric(b, "mfu").update(bound=0.2),
    "why on a metric": lambda b: _metric(b, "mfu").update(why="because"),
    "unit with a space": lambda b: _metric(b, "mfu").update(unit="a b"),
    "no setup_s": lambda b: b["end_to_end"].remove(_metric(b, "setup_s")),
    "moves nothing": lambda b: _metric(
        b, "step.device_ms").update(moves="nothing"),
    "metric lists an unknown cell": lambda b: _metric(
        b, "step.device_ms").update(workloads=["nowhere"]),
    "chips 2": lambda b: b["workloads"][0].update(chips=2),
    "why of 201 characters": lambda b: b["workloads"][0].update(
        why="x" * 201),
    "cell of an unknown configuration": lambda b: b["workloads"][0].update(
        config="nothing"),
    "pair appears twice": lambda b: b["workloads"].append(
        dict(b["workloads"][0], name="again")),
    "two cells share a name": lambda b: b["workloads"].append(
        dict(b["workloads"][0], traffic="other")),
    "config file outside paths": lambda b: b["configs"][0].update(
        file="configs/x.json"),
    "unused configuration": lambda b: b["configs"].append(
        dict(b["configs"][0], name="spare", file="benchmarks/configs/s.json")),
    "too many four-chip cells": lambda b: [
        w.update(chips=4) for w in b["workloads"]],
    "end-to-end from a program counter": lambda b: _metric(
        b, "mfu").update(source="program_counter"),
}


@pytest.mark.parametrize("case", sorted(BAD_BENCHMARKS))
def test_each_breach_of_the_contract_is_found(case):
    b = copy.deepcopy(bench())
    BAD_BENCHMARKS[case](b)
    assert validate.check_benchmark(b), case


def test_every_reader_file_agrees_with_its_entry():
    """BENCHMARK.json's per-layer entries and the readers' declarations say
    the same thing, and every entry has its reader."""
    b = bench()
    readers = spec.declared_layer_metrics()
    cells = {w["name"]: w for w in b["workloads"]}
    for entry in b["per_layer"]:
        reader = readers[entry["name"]]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"]) == (
            reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
            reader.MOVES), entry["name"]
        for name in entry.get("workloads", cells):
            cell = spec.load_cell(name)
            assert reader.DRIVERS is None \
                or cell.traffic["driver"] in reader.DRIVERS
            assert reader.CHIPS is None or cell.chips in reader.CHIPS
    for entry in b["end_to_end"]:
        assert os.path.isfile(os.path.join(
            REPO, "benchmarks", "end_to_end", entry["name"] + ".py"))


@pytest.mark.parametrize("workload", [w["name"] for w in bench()["workloads"]])
def test_every_cell_finds_its_files_by_name(workload):
    cell = spec.load_cell(workload)
    assert callable(cell.driver().start)
    assert cell.train_flops_per_image() > 0 and cell.parameter_count() > 0
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer
    for name in cell.per_layer:
        assert callable(spec.load_module("layer_metrics", name,
                                         cell.bench_dir).read)
    assert cell.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        cell.peak("TPU v9 imaginary")


@pytest.mark.parametrize("config", [c["name"] for c in bench()["configs"]])
def test_a_configuration_file_lists_the_reductions_its_entry_lists(config):
    """`reduced` names the same keys in BENCHMARK.json and in the file, each
    a top-level key of the file; name and source agree too."""
    entry = next(c for c in bench()["configs"] if c["name"] == config)
    with open(os.path.join(REPO, entry["file"])) as f:
        held = json.load(f)
    assert sorted(held["reduced"]) == sorted(entry["reduced"])
    assert set(entry["reduced"]) <= set(held)
    assert held["name"] == config and held["source"] == entry["source"]


def test_every_reader_file_has_an_entry():
    """No reader, traffic mix or driver ships for a cell the benchmark does
    not have: it comes with the PR that adds the cell."""
    assert sorted(spec.declared_layer_metrics()) == sorted(
        m["name"] for m in bench()["per_layer"])
    for kind in ("traffic", "drivers"):
        used = {spec.load_cell(w["name"]).traffic["driver"] if kind ==
                "drivers" else w["traffic"] for w in bench()["workloads"]}
        held = {os.path.splitext(f)[0] for f in os.listdir(
            os.path.join(REPO, "benchmarks", kind)) if not f.startswith("_")}
        assert held == used, kind
