"""The state-space scans' share of their roofline in the Nemotron cell, in
percent (``harness/nemotron_scopes.py:scan_roofline``): what the mathematics
of a step's scans needs (five layers, forward and backward; the chunked
form's matmuls, each operand read and each result written once;
``ops_count/nemotron_h.py:ssm_scan_cost``, from the shapes alone) at the
chip's peaks, against the device time under the ``ssm_scan`` scope. It reads
the same work whatever implements the scan: XLA's programs today, a kernel
later."""

from harness import nemotron_scopes

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "mfu"
DRIVERS = ("sync_mesh_lm",)
CHIPS = None


def read(run):
    return nemotron_scopes.scan_roofline(run)
