"""Durable telemetry journal (ISSUE 18): segmented JSONL writer/reader,
torn-write recovery, rotation + retention downsampling, the process
hub, and the ``/fleet`` incremental-polling protocol.

Everything here is tier-1: temp directories, fake clocks, in-process
HTTP on loopback — no accelerator, no subprocesses. The live
multi-process incident assertions live in the slow recorded-demo
wrapper test.
"""

from __future__ import annotations

import json
import os
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
import threading

import pytest

from distributed_parameter_server_for_ml_training_tpu.cli import (
    _merge_top_history,
)
from distributed_parameter_server_for_ml_training_tpu.telemetry import (
    EVENT_CATALOG,
    FleetCollector,
    JournalReader,
    JournalWriter,
    LATENCY_BUCKETS,
    MetricsRegistry,
    SnapshotEmitter,
    get_journal,
    histogram_quantile,
    journal_event,
    read_journal,
    set_journal,
    start_fleet_server,
)
from distributed_parameter_server_for_ml_training_tpu.telemetry. \
    prometheus import render_prometheus


def _writer(directory, **kw):
    kw.setdefault("registry", MetricsRegistry())
    return JournalWriter(str(directory), **kw)


@pytest.fixture(autouse=True)
def _clean_hub():
    yield
    set_journal(None)


# -- writer/reader roundtrip -------------------------------------------------

def test_append_read_roundtrip(tmp_path):
    w = _writer(tmp_path, role="server")
    w.append("alert", {"rule": "worker_stale", "severity": "critical",
                       "state": "fired"})
    w.append("checkpoint", {"step": 7, "path": "ckpt/step7"})
    w.seal()
    recs = read_journal(str(tmp_path))
    assert [r["type"] for r in recs] == ["alert", "checkpoint"]
    env = recs[0]
    assert env["v"] == 1 and env["role"] == "server"
    assert env["pid"] == os.getpid() and env["seq"] == 1
    assert recs[1]["step"] == 7


def test_envelope_beats_payload_but_payload_ts_wins(tmp_path):
    w = _writer(tmp_path, role="server")
    rec = w.append("snapshot", {"ts": 123.0, "role": "spoofed",
                                "seq": 999, "counters": {}})
    assert rec["ts"] == 123.0          # payload timestamp is the event time
    assert rec["role"] == "server"     # envelope owns identity fields
    assert rec["seq"] == 1


def test_unknown_type_rejected(tmp_path):
    w = _writer(tmp_path)
    with pytest.raises(ValueError, match="unknown journal event type"):
        w.append("not_a_type", {})
    assert "snapshot" in EVENT_CATALOG and "incident" in EVENT_CATALOG


def test_reader_filters(tmp_path):
    clock = iter(float(i) for i in range(1, 10))
    w = _writer(tmp_path, role="server", clock=lambda: next(clock))
    for _ in range(3):
        w.append("snapshot", {"counters": {}})
    w.append("alert", {"rule": "r", "state": "fired"})
    w.seal()
    assert len(read_journal(str(tmp_path), types=("alert",))) == 1
    assert len(read_journal(str(tmp_path), roles=("worker",))) == 0
    mid = read_journal(str(tmp_path), start_ts=2.0, end_ts=3.0)
    assert [r["ts"] for r in mid] == [2.0, 3.0]


# -- torn-write recovery -----------------------------------------------------

def test_torn_tail_skipped_not_fatal(tmp_path):
    w = _writer(tmp_path, role="server")
    w.append("alert", {"rule": "a", "state": "fired"})
    w.append("alert", {"rule": "b", "state": "fired"})
    w.seal()
    seg = [p for p in os.listdir(tmp_path) if p.endswith(".jsonl")][0]
    with open(tmp_path / seg, "a", encoding="utf-8") as f:
        f.write('{"v": 1, "type": "alert", "ts": 9')  # SIGKILL mid-line
    reader = JournalReader(str(tmp_path))
    recs = reader.records()
    assert [r["rule"] for r in recs] == ["a", "b"]
    assert reader.stats["torn_tails"] == 1
    assert reader.stats["corrupt_lines"] == 0


def test_corrupt_midfile_line_skipped(tmp_path):
    w = _writer(tmp_path, role="server")
    w.append("alert", {"rule": "a", "state": "fired"})
    w.append("alert", {"rule": "b", "state": "fired"})
    w.seal()
    seg = tmp_path / [p for p in os.listdir(tmp_path)
                      if p.endswith(".jsonl")][0]
    lines = seg.read_text().splitlines()
    lines.insert(1, "\x00garbage not json\x00")
    seg.write_text("\n".join(lines) + "\n")
    reader = JournalReader(str(tmp_path))
    recs = reader.records()
    assert [r["rule"] for r in recs] == ["a", "b"]
    assert reader.stats["corrupt_lines"] == 1
    assert reader.stats["torn_tails"] == 0


# -- rotation + retention ----------------------------------------------------

def test_rotation_by_size(tmp_path):
    w = _writer(tmp_path, max_segment_bytes=256)
    for i in range(20):
        w.append("alert", {"rule": f"r{i}", "state": "fired",
                           "pad": "x" * 64})
    w.seal()
    segs = [p for p in os.listdir(tmp_path) if p.endswith(".jsonl")]
    assert len(segs) > 1
    # lexicographic order == chronological order (the naming contract)
    recs = read_journal(str(tmp_path))
    assert [r["seq"] for r in recs] == list(range(1, 21))


def test_rotation_by_age(tmp_path):
    t = [1000.0]
    w = _writer(tmp_path, max_segment_age_s=10.0, clock=lambda: t[0])
    w.append("alert", {"rule": "a", "state": "fired"})
    t[0] += 60.0
    w.append("alert", {"rule": "b", "state": "fired"})
    w.seal()
    segs = [p for p in os.listdir(tmp_path) if p.endswith(".jsonl")]
    assert len(segs) == 2


def _snapshot_payload(i, n_events=20):
    """A growing cumulative histogram: event j observed 0.001 * (j+1)."""
    le = list(LATENCY_BUCKETS)
    counts = [0] * (len(le) + 1)
    total = 0
    ssum = 0.0
    for j in range(i * n_events):
        v = 0.001 * (j % 40 + 1)
        k = next((idx for idx, edge in enumerate(le) if v <= edge),
                 len(le))
        counts[k] += 1
        total += 1
        ssum += v
    return {"ts": 1000.0 + i,
            "histograms": {"dps_h": {"le": le, "counts": counts,
                                     "sum": ssum, "count": total}}}


def test_retention_downsamples_into_coarse_tier(tmp_path):
    # Retention is enforced when a segment is sealed by rotation, so the
    # sizes must force a rotation with more than the cap sealed whatever
    # the record length: every line carries the pid, and with 39 records
    # (12 KB, just under three segments for a pid of one to three digits)
    # a short pid left the third segment active and nothing to compact.
    # 59 records are 19 KB: the third rotation finds 11 KB sealed.
    n = 60
    w = _writer(tmp_path, max_segment_bytes=4096, retention_bytes=8192,
                coarse_keep_every=5)
    for i in range(1, n):
        w.append("snapshot", _snapshot_payload(i))
        if i % 10 == 0:
            w.append("alert", {"rule": f"r{i}", "state": "fired"})
    w.seal()
    names = os.listdir(tmp_path)
    coarse = [name for name in names if name.endswith(".coarse.jsonl")]
    raw = [name for name in names
           if name.endswith(".jsonl") and name not in coarse]
    assert coarse, "retention never compacted a segment"
    raw_bytes = sum(os.path.getsize(tmp_path / name) for name in raw)
    assert raw_bytes <= 8192 + 4096  # cap + one active segment of slack
    # ALL non-snapshot events survive downsampling — they ARE the record.
    alerts = read_journal(str(tmp_path), types=("alert",))
    assert [r["rule"] for r in alerts] == [f"r{i}" for i in range(10, n, 10)]
    # snapshots thinned, not emptied
    snaps = read_journal(str(tmp_path), types=("snapshot",))
    assert 0 < len(snaps) < n - 1


def test_downsample_percentiles_stay_exact(tmp_path):
    """Cumulative payloads make kept samples exact: the percentile at
    any KEPT tick equals the raw percentile at the same tick —
    downsampling coarsens time resolution, never the counts."""
    w = _writer(tmp_path, max_segment_bytes=1 << 20,
                coarse_keep_every=4)
    for i in range(1, 13):
        w.append("snapshot", _snapshot_payload(i))
    w.seal()
    raw_by_ts = {r["ts"]: r for r in read_journal(str(tmp_path))}
    seg = tmp_path / [n for n in os.listdir(tmp_path)
                      if n.endswith(".jsonl")][0]
    w._compact_segment(str(seg))
    kept = read_journal(str(tmp_path), types=("snapshot",))
    assert len(kept) < 12
    assert kept[-1]["ts"] == 1012.0  # newest sample always survives
    for rec in kept:
        h, raw_h = (rec["histograms"]["dps_h"],
                    raw_by_ts[rec["ts"]]["histograms"]["dps_h"])
        for p in (50, 95, 99):
            assert histogram_quantile(h["le"], h["counts"], p) == \
                histogram_quantile(raw_h["le"], raw_h["counts"], p)
        assert h["count"] == raw_h["count"]


# -- process hub -------------------------------------------------------------

def test_hub_is_noop_when_unset(tmp_path):
    set_journal(None)
    journal_event("alert", rule="r", state="fired")  # must not raise
    assert get_journal() is None


def test_hub_writes_and_never_raises(tmp_path):
    w = _writer(tmp_path, role="server")
    set_journal(w)
    assert get_journal() is w
    journal_event("directive", worker="w0", action="pause", seq=1)
    journal_event("not_a_type", x=1)  # swallowed, not ValueError
    set_journal(None)
    w.seal()
    recs = read_journal(str(tmp_path))
    assert len(recs) == 1 and recs[0]["worker"] == "w0"


def test_snapshot_emitter_journals_and_seals(tmp_path):
    reg = MetricsRegistry()
    reg.counter("dps_test_total").inc(3)
    w = _writer(tmp_path, role="server", registry=MetricsRegistry())
    em = SnapshotEmitter(registry=reg, interval=60.0, role="server",
                         journal=w)
    em.emit_once()
    em.stop(final=True)
    assert w._fh is None  # sealed: crash-consistent fsync'd tail
    recs = read_journal(str(tmp_path), types=("snapshot",))
    assert len(recs) == 2  # the explicit emit + stop()'s final flush
    assert recs[-1]["counters"]["dps_test_total"] == 3
    assert "kind" not in recs[-1]  # journal form drops the line marker


# -- /fleet ?since incremental polling --------------------------------------

class _FakeProc:
    """Minimal /metrics target for the collector."""

    def __init__(self):
        self.registry = MetricsRegistry()
        outer = self

        class H(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server API)
                path = self.path.partition("?")[0]
                if path == "/metrics.json":
                    body = json.dumps(outer.registry.snapshot()).encode()
                elif path == "/metrics":
                    body = render_prometheus(outer.registry).encode()
                else:
                    self.send_error(404)
                    return
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass

        self.server = ThreadingHTTPServer(("localhost", 0), H)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.server.shutdown()


def _get(url):
    return json.loads(urllib.request.urlopen(url, timeout=5).read())


def test_fleet_since_slices_history(tmp_path):
    proc = _FakeProc()
    proc.registry.counter("dps_store_fetches_total", backend="p").inc(1)
    col = FleetCollector([f"localhost:{proc.port}"], interval_s=0.05,
                         timeout_s=2.0, registry=MetricsRegistry())
    server, port = start_fleet_server(col, port=0, addr="localhost")
    try:
        for _ in range(3):
            col.tick()
        base = f"http://localhost:{port}/fleet"
        full = _get(base)
        assert full["ticks"] == 3
        assert "history_since" not in full
        assert len(full["history"]["fleet_qps"]) == 3
        delta = _get(base + "?since=1")
        assert delta["history_since"] == 1
        assert len(delta["history"]["fleet_qps"]) == 2
        assert delta["history"]["fleet_qps"] == \
            full["history"]["fleet_qps"][-2:]
        # caller already current -> empty rows, cheap poll
        cur = _get(base + "?since=3")
        assert cur["history"]["fleet_qps"] == []
        # bogus since values degrade to the full payload
        assert len(_get(base + "?since=junk")["history"]["fleet_qps"]) \
            == 3
    finally:
        server.shutdown()
        proc.stop()


def test_merge_top_history_incremental_and_degraded():
    v1 = {"ticks": 3, "history": {"fleet_qps": [1, 2, 3]}}
    local = _merge_top_history(None, v1, None)
    assert v1["history"]["fleet_qps"] == [1, 2, 3]
    # capable server: delta appended onto the local rings
    v2 = {"ticks": 5, "history_since": 3,
          "history": {"fleet_qps": [4, 5]}}
    local = _merge_top_history(local, v2, 3)
    assert v2["history"]["fleet_qps"] == [1, 2, 3, 4, 5]
    # old server: no history_since marker -> full replacement
    v3 = {"ticks": 6, "history": {"fleet_qps": [9, 9]}}
    local = _merge_top_history(local, v3, 5)
    assert v3["history"]["fleet_qps"] == [9, 9]
    # collector restart: ticks went backwards -> full replacement
    v4 = {"ticks": 1, "history_since": 6,
          "history": {"fleet_qps": [7]}}
    _merge_top_history(local, v4, 6)
    assert v4["history"]["fleet_qps"] == [7]
