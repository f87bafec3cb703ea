"""benchwatch: schema-validated bench ledger + regression watch.

Root-level ``BENCH_r*.json`` / ``MULTICHIP_r*.json`` records are a
longitudinal performance record. This tool ingests whatever ledger is
there (none at all is a pass, not an error), validates every record
against the schema the bench harness actually emits, and runs a
noise-tolerant regression check:

- **Usable** records: ``rc == 0``, non-null ``parsed``, and no
  ``platform_fallback`` marker (a CPU-fallback number is not comparable
  to TPU history). Unusable records are SKIPPED AND REPORTED with a
  reason — an rc!=0 backend-init flake is not a regression, but it is
  not silently dropped either.
- **Regression** per metric: the median of the newest
  ``recent_window`` usable values vs the median of the
  ``baseline_window`` values before them; flagged when recent <
  baseline x (1 - tolerance) for higher-is-better series, or recent >
  baseline x (1 + tolerance) for lower-is-better overheads (see
  ``EXTRA_METRIC_FIELDS``). Medians tolerate single-run noise; the
  windows are configurable per invocation.

Surfaces: ``python -m tools.benchwatch`` (scripts/lint.sh gate 4 runs
``--validate-only``; scripts/tier1.sh runs the full check) and
``cli perf check`` (same code, same verdict). Exit codes: 0 pass,
1 malformed ledger, 2 regression. Deliberately jax-free so the lint
gate stays cheap.
"""

from __future__ import annotations

import glob
import json
import os
import statistics

__all__ = [
    "EXTRA_METRIC_FIELDS",
    "check_regressions",
    "load_ledger",
    "load_profile_ledger",
    "render_markdown",
    "validate_profile_record",
    "validate_record",
]

#: field -> required type(s) for the two record kinds (the shape
#: bench.py emits and the committed history carries; ``parsed`` extras
#: beyond the core four keys are allowed — newer bench.py versions
#: append fields like fetch_qps/mfu and old records must stay valid).
_BENCH_FIELDS = {"n": int, "cmd": str, "rc": int, "tail": str}
_PARSED_FIELDS = {"metric": str, "value": (int, float), "unit": str}
_MULTICHIP_FIELDS = {"n_devices": int, "rc": int, "ok": bool,
                     "skipped": bool, "tail": str}

#: Secondary series lifted out of ``parsed`` extras and watched
#: alongside the headline metric: field name -> unit string (plain
#: higher-is-better series) or ``{"unit", "direction": "lower"}`` for
#: overheads that regress UPWARD (recent > baseline x (1 + tolerance)).
#: Optional by design — records that predate a field (or record it
#: null) simply don't contribute a point, so a new field starts at
#: insufficient_history and only gates once enough rounds carry it.
#: ``codec_mb_per_s`` (ISSUE 14) is the device-resident push codec's
#: encode throughput; ``fanout_qps`` (ISSUE 17) is the edge-replica
#: delta-serve rate of the two-tier fan-out probe;
#: ``journal_write_us``/``journal_bytes_per_tick`` (ISSUE 18) are the
#: durable journal's per-record append latency and per-snapshot disk
#: cost — both lower-is-better, gating the <2% overhead claim.
#: ``goodput_fraction`` (ISSUE 20) is the productive fraction of the
#: bench's timed-trial wall (harness overhead shows up as the gap below
#: 1.0) — higher-is-better like the headline metric.
EXTRA_METRIC_FIELDS = {"codec_mb_per_s": "MB/s",
                       "fanout_qps": "fetch/s",
                       "journal_write_us": {"unit": "us",
                                            "direction": "lower"},
                       "journal_bytes_per_tick": {"unit": "B",
                                                  "direction": "lower"},
                       "goodput_fraction": "fraction"}


def _field_spec(spec) -> tuple[str, str]:
    """(unit, direction) for one EXTRA_METRIC_FIELDS value — a bare
    string means higher-is-better, the dict form names its direction."""
    if isinstance(spec, dict):
        return str(spec.get("unit", "")), str(spec.get("direction",
                                                       "higher"))
    return str(spec), "higher"


def _type_errors(obj: dict, fields: dict, ctx: str) -> list:
    errs = []
    for key, typ in fields.items():
        if key not in obj:
            errs.append(f"{ctx}: missing required field {key!r}")
        elif not isinstance(obj[key], typ) or isinstance(obj[key], bool) \
                and typ is int:
            errs.append(f"{ctx}: field {key!r} has type "
                        f"{type(obj[key]).__name__}, wanted "
                        f"{getattr(typ, '__name__', typ)}")
    return errs


def validate_record(kind: str, obj) -> list:
    """Schema errors for one record ('' list = valid). ``kind`` is
    'bench' or 'multichip'."""
    if not isinstance(obj, dict):
        return [f"{kind} record is {type(obj).__name__}, wanted object"]
    if kind == "multichip":
        return _type_errors(obj, _MULTICHIP_FIELDS, "multichip")
    errs = _type_errors(obj, _BENCH_FIELDS, "bench")
    if "parsed" not in obj:
        errs.append("bench: missing required field 'parsed'")
    elif obj["parsed"] is not None:
        if not isinstance(obj["parsed"], dict):
            errs.append("bench: 'parsed' must be null or object")
        else:
            errs += _type_errors(obj["parsed"], _PARSED_FIELDS,
                                 "bench.parsed")
            if "vs_baseline" not in obj["parsed"]:
                errs.append("bench.parsed: missing required field "
                            "'vs_baseline'")
    return errs


def load_ledger(root: str) -> dict:
    """All committed records under ``root``, in run order, each entry
    ``{"file", "kind", "record"|None, "errors": [...]}``."""
    entries = []
    for kind, pat in (("bench", "BENCH_*.json"),
                      ("multichip", "MULTICHIP_*.json")):
        for path in sorted(glob.glob(os.path.join(root, pat))):
            entry = {"file": os.path.basename(path), "kind": kind,
                     "record": None, "errors": []}
            try:
                with open(path) as f:
                    obj = json.load(f)
            except (OSError, json.JSONDecodeError) as e:
                entry["errors"] = [f"unreadable: {e}"]
                entries.append(entry)
                continue
            entry["record"] = obj
            entry["errors"] = validate_record(kind, obj)
            entries.append(entry)
    return {"root": root, "entries": entries,
            "malformed": [e for e in entries if e["errors"]]}


#: Required shape of one committed ``profiles/PROFILE_*.json`` ledger
#: record (the ProfileTrigger writes these; field semantics are
#: drift-pinned in telemetry/proftrigger.py PROFILE_RECORD_FIELDS —
#: NOT imported here, benchwatch stays jax-free by construction).
_PROFILE_FIELDS = {"id": str, "created_ts": (int, float), "rule": str,
                   "profile": dict}


def validate_profile_record(obj) -> list:
    """Schema errors for one profile-ledger record ('' list = valid)."""
    if not isinstance(obj, dict):
        return [f"profile record is {type(obj).__name__}, wanted object"]
    errs = _type_errors(obj, _PROFILE_FIELDS, "profile")
    prof = obj.get("profile")
    if isinstance(prof, dict):
        ocs = prof.get("op_classes")
        if not isinstance(ocs, dict):
            errs.append("profile.profile: missing 'op_classes' object")
        else:
            for cls, row in ocs.items():
                t = row.get("time_s") if isinstance(row, dict) else None
                if not isinstance(t, (int, float)) \
                        or isinstance(t, bool):
                    errs.append(f"profile.profile.op_classes[{cls!r}]: "
                                f"missing numeric 'time_s'")
    return errs


def load_profile_ledger(root: str) -> dict:
    """All committed ``PROFILE_*.json`` records under ``root``, oldest
    first (the id stamp sorts lexically), same entry shape as
    :func:`load_ledger`."""
    entries = []
    for path in sorted(glob.glob(os.path.join(root, "PROFILE_*.json"))):
        entry = {"file": os.path.basename(path), "kind": "profile",
                 "record": None, "errors": []}
        try:
            with open(path) as f:
                obj = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            entry["errors"] = [f"unreadable: {e}"]
            entries.append(entry)
            continue
        entry["record"] = obj
        entry["errors"] = validate_profile_record(obj)
        entries.append(entry)
    return {"root": root, "entries": entries,
            "malformed": [e for e in entries if e["errors"]]}


def _profile_points(profile_ledger: dict, skipped: list) -> dict:
    """Per-op-class ``time_s`` series from the profile ledger, keyed
    ``profile:<class>.time_s`` (lower-is-better — a class whose device
    time grows across captures regressed). Bases must agree to compare:
    records whose attribution basis differs from the NEWEST usable
    record's are skipped and reported, never silently mixed — the same
    honesty rule ``cli perf diff`` enforces with a refusal."""
    usable = []
    for entry in profile_ledger["entries"]:
        if entry["errors"]:
            continue
        rec = entry["record"]
        basis = (rec.get("profile") or {}).get("basis")
        if basis in (None, "none"):
            skipped.append({"file": entry["file"],
                            "reason": "basis=none (attribution failed; "
                                      "not comparable)"})
            continue
        usable.append((entry["file"], basis, rec))
    if not usable:
        return {}
    ref_basis = usable[-1][1]
    by_metric: dict[str, list] = {}
    for fname, basis, rec in usable:
        if basis != ref_basis:
            skipped.append({"file": fname,
                            "reason": f"basis={basis!r} != newest "
                                      f"{ref_basis!r} (different "
                                      f"measurements; not comparable)"})
            continue
        for cls, row in rec["profile"]["op_classes"].items():
            by_metric.setdefault(f"profile:{cls}.time_s", []).append(
                {"file": fname, "value": float(row["time_s"]),
                 "unit": "s", "direction": "lower"})
    return by_metric


def _usable_bench(entry: dict) -> tuple[bool, str]:
    """(usable, reason-if-not) for one valid bench entry."""
    rec = entry["record"]
    if rec.get("rc") != 0:
        return False, f"rc={rec.get('rc')} (run failed; not comparable)"
    parsed = rec.get("parsed")
    if not isinstance(parsed, dict):
        return False, "parsed=null (no metric extracted)"
    if parsed.get("platform_fallback"):
        return False, (f"platform_fallback="
                       f"{parsed.get('platform_fallback')!r} "
                       f"(not comparable to accelerator history)")
    return True, ""


def check_regressions(ledger: dict, tolerance: float = 0.05,
                      baseline_window: int = 3,
                      recent_window: int = 1,
                      profile_ledger: dict | None = None) -> dict:
    """The verdict over one loaded ledger (see module docstring). With
    ``profile_ledger`` (:func:`load_profile_ledger`), the committed
    per-op-class ``time_s`` series regression-check alongside the bench
    metrics — lower-is-better, same median windows."""
    if tolerance < 0 or baseline_window < 1 or recent_window < 1:
        raise ValueError("tolerance must be >= 0 and windows >= 1")
    skipped = []
    by_metric: dict[str, list] = {}
    if profile_ledger is not None:
        by_metric.update(_profile_points(profile_ledger, skipped))
    for entry in ledger["entries"]:
        if entry["kind"] != "bench" or entry["errors"]:
            continue
        ok, reason = _usable_bench(entry)
        if not ok:
            skipped.append({"file": entry["file"], "reason": reason})
            continue
        parsed = entry["record"]["parsed"]
        by_metric.setdefault(parsed["metric"], []).append(
            {"file": entry["file"], "value": float(parsed["value"]),
             "unit": parsed.get("unit", ""), "direction": "higher"})
        for field, spec in EXTRA_METRIC_FIELDS.items():
            unit, direction = _field_spec(spec)
            v = parsed.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                by_metric.setdefault(field, []).append(
                    {"file": entry["file"], "value": float(v),
                     "unit": unit, "direction": direction})
    metrics = {}
    regressions = []
    for metric, points in by_metric.items():
        values = [p["value"] for p in points]
        direction = points[0].get("direction", "higher")
        row: dict = {"unit": points[0]["unit"], "runs": len(points),
                     "values": values, "direction": direction,
                     "files": [p["file"] for p in points]}
        if len(values) < baseline_window + recent_window:
            row["status"] = "insufficient_history"
            row["needed"] = baseline_window + recent_window
        else:
            recent = statistics.median(values[-recent_window:])
            base = statistics.median(
                values[-(recent_window + baseline_window):-recent_window])
            if direction == "lower":
                ceiling = base * (1.0 + tolerance)
                regressed = recent > ceiling
                bound = {"ceiling": round(ceiling, 3)}
            else:
                floor = base * (1.0 - tolerance)
                regressed = recent < floor
                bound = {"floor": round(floor, 3)}
            row.update({
                "recent_median": round(recent, 3),
                "baseline_median": round(base, 3),
                "change_fraction": round((recent - base) / base, 4)
                if base else None,
                "status": "regression" if regressed else "ok",
                **bound,
            })
            if row["status"] == "regression":
                regressions.append(metric)
        metrics[metric] = row
    malformed = [{"file": e["file"], "errors": e["errors"]}
                 for e in ledger["malformed"]]
    if profile_ledger is not None:
        malformed += [{"file": e["file"], "errors": e["errors"]}
                      for e in profile_ledger["malformed"]]
    status = "malformed" if malformed else (
        "regression" if regressions else "pass")
    return {
        "status": status,
        "tolerance": tolerance,
        "baseline_window": baseline_window,
        "recent_window": recent_window,
        "metrics": metrics,
        "regressions": sorted(regressions),
        "skipped": skipped,
        "malformed": malformed,
    }


def render_markdown(verdict: dict) -> str:
    """Markdown verdict for humans / PR comments."""
    icon = {"pass": "PASS", "regression": "REGRESSION",
            "malformed": "MALFORMED LEDGER"}
    label = icon.get(verdict["status"], verdict["status"])
    lines = [f"## benchwatch: {label}", ""]
    if verdict["metrics"]:
        lines += ["| metric | runs | baseline | recent | change | "
                  "status |", "|---|---|---|---|---|---|"]
        for name in sorted(verdict["metrics"]):
            m = verdict["metrics"][name]
            if m["status"] == "insufficient_history":
                lines.append(f"| `{name}` | {m['runs']} | - | - | - | "
                             f"insufficient history "
                             f"(need {m['needed']}) |")
                continue
            chg = m["change_fraction"]
            chg_s = "-" if chg is None else f"{chg*100:+.1f}%"
            lines.append(
                f"| `{name}` | {m['runs']} | {m['baseline_median']} | "
                f"{m['recent_median']} | {chg_s} | {m['status']} |")
    else:
        lines.append("_no usable bench records_")
    if verdict["skipped"]:
        lines += ["", "Skipped records (reported, never compared):"]
        lines += [f"- `{s['file']}`: {s['reason']}"
                  for s in verdict["skipped"]]
    if verdict["malformed"]:
        lines += ["", "Malformed records (fail the gate):"]
        lines += [f"- `{m['file']}`: {'; '.join(m['errors'])}"
                  for m in verdict["malformed"]]
    lines += ["", f"tolerance {verdict['tolerance']*100:.0f}% · baseline "
                  f"window {verdict['baseline_window']} · recent window "
                  f"{verdict['recent_window']}"]
    return "\n".join(lines)
