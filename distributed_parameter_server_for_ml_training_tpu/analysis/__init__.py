"""Experiment/analysis layer (reference L5: scripts/)."""

from .parse_logs import (
    aggregate_worker_metrics,
    alert_timeline,
    build_telemetry_timeseries,
    cluster_worker_series,
    parse_cluster_series,
    parse_experiment,
    parse_snapshot_series,
    staleness_series,
    worker_throughput_series,
)
from .device_profile import (
    OP_CLASSES,
    attribute_profile,
    classify_op,
    device_time_tables,
    diff_profiles,
    load_chrome_trace,
    render_profile_diff,
    render_profile_table,
)
from .fleet_series import extract_exemplars, resolve_exemplars
from .incidents import (
    PHASE_ORDER,
    build_timeline,
    classify_event,
    describe_event,
    list_incidents,
    load_incident,
    render_timeline,
)
from .runner import run_cell, run_matrix
from .traces import (
    PHASES,
    assemble_traces,
    critical_path_report,
    find_trace_dumps,
    load_trace_dumps,
    ps_phase_report,
    render_ps_phase_table,
    save_chrome_trace,
    to_chrome_trace,
)
from .visualize import ExperimentVisualizer

__all__ = ["OP_CLASSES", "PHASES", "PHASE_ORDER",
           "aggregate_worker_metrics", "alert_timeline",
           "assemble_traces", "attribute_profile",
           "build_telemetry_timeseries", "build_timeline",
           "classify_event", "classify_op",
           "cluster_worker_series",
           "critical_path_report", "describe_event",
           "device_time_tables", "diff_profiles",
           "extract_exemplars",
           "list_incidents", "load_incident", "render_timeline",
           "find_trace_dumps", "load_chrome_trace", "load_trace_dumps",
           "resolve_exemplars",
           "parse_cluster_series",
           "parse_experiment", "parse_snapshot_series",
           "ps_phase_report",
           "render_profile_diff", "render_profile_table",
           "render_ps_phase_table",
           "save_chrome_trace", "staleness_series", "to_chrome_trace",
           "worker_throughput_series",
           "ExperimentVisualizer", "run_cell", "run_matrix"]
