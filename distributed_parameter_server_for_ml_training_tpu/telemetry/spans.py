"""Span helpers: wall-time instrumentation for the hot paths.

A "span" here is deliberately minimal — a duration observed into a fixed-
bucket histogram plus an optional call counter — not a distributed-tracing
tree. The hot paths this framework cares about (train step, push/fetch RPC
client+handler, store aggregation) are flat and high-frequency; what the
adaptive-sync literature needs from them is *distributions over time*
(PAPERS.md: ACE-Sync consumes staleness/latency signals), which histograms
in the snapshot stream deliver at microsecond record cost.

Two usage shapes:

- ``with span(hist):`` for paths where a context manager's ~1 us overhead
  is irrelevant (RPC handlers, epoch loops);
- ``t0 = now(); ...; hist.observe(now() - t0)`` inlined where every
  nanosecond is on-budget (store push/fetch). ``now`` is re-exported
  ``time.perf_counter`` so call sites don't import ``time`` twice.

For deep profiler traces use telemetry/profiler.py (jax.profiler) — spans and
traces answer different questions (always-on time-series vs one-off
timeline).
"""

from __future__ import annotations

from contextlib import contextmanager
from time import perf_counter as now

from .registry import Counter, Histogram

__all__ = ["span", "now"]


@contextmanager
def span(hist: Histogram, counter: Counter | None = None):
    """Observe the block's wall time into ``hist`` (and bump ``counter``).

    The duration is recorded even when the body raises — a failing RPC
    still spent the wire time, and dropping error durations would bias the
    distribution toward the happy path.
    """
    t0 = now()
    try:
        yield
    finally:
        hist.observe(now() - t0)
        if counter is not None:
            counter.inc()
