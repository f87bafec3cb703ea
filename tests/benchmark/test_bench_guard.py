"""The stdout guard: whatever prints after the result, from any thread or
from C, the standard output still ends on the result line."""

import json
import os
import subprocess
import sys

import pytest

from harness import spec, validate

CHILD = """
import os, sys, threading, time
sys.path.insert(0, {bench!r})
from harness.guard import StdoutGuard
print("before the guard")            # the real stdout, before the result
guard = StdoutGuard({log!r})

def chatter():
    while True:
        print("EPOCH_DONE worker=late", flush=True)
        os.write(1, b"METRICS_JSON from C\\n")
        time.sleep(0.001)

threading.Thread(target=chatter, daemon=False).start()
import atexit
atexit.register(lambda: print("atexit line", flush=True))
time.sleep(0.05)
{ending}
"""

RESULT = {"correct": True, "attempted": 3, "failed": 0,
          "metrics": {"setup_s": {"value": 1.0, "unit": "s"}},
          "device": {"platform": "tpu", "kind": "k", "count": 1,
                     "memory_peak_bytes": 1}}


def _child(tmp_path, ending):
    log = str(tmp_path / "out" / "run.log")
    code = CHILD.format(bench=spec.BENCH_DIR, log=log, ending=ending)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60)
    with open(log) as f:
        return p, f.read()


def test_nothing_follows_the_result_line(tmp_path):
    p, log = _child(tmp_path, f"guard.emit_and_exit({RESULT!r})")
    assert p.returncode == 0
    lines = p.stdout.split("\n")
    assert lines[0] == "before the guard"
    assert json.loads(lines[-2]) == RESULT and lines[-1] == ""
    assert validate.check_last_line(p.stdout, owed={"setup_s": "s"},
                                    trace=False) == []
    assert "EPOCH_DONE worker=late" in log and "METRICS_JSON from C" in log
    assert "EPOCH_DONE" not in p.stdout and "atexit" not in p.stdout


@pytest.mark.parametrize("code", [1, 3])
def test_a_failed_run_prints_no_result_and_exits_non_zero(tmp_path, code):
    p, _log = _child(tmp_path, f"guard.fail('no accelerator', {code})")
    assert p.returncode == code
    assert p.stdout == "before the guard\n"
    assert "benchmark: no accelerator" in p.stderr


def test_a_result_that_json_cannot_carry_is_refused(tmp_path):
    bad = dict(RESULT, attempted=float("nan"))
    p, _log = _child(tmp_path, f"guard.emit_and_exit({bad!r})".replace(
        "nan", "float('nan')"))
    assert p.returncode != 0 and "correct" not in p.stdout
