"""The ``flash_attention_bwd_dq`` Pallas kernel's share of its roofline, in percent
(``harness/hlo_scopes.py:kernel_roofline``): what the mathematics needs
(causal half, true head widths, no padding; ``ops_count/``) against the
device time of the kernel's events in the traced slice."""

from harness import hlo_scopes

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "mfu"
DRIVERS = ("sync_mesh_tokens",)
CHIPS = None


def read(run):
    return hlo_scopes.kernel_roofline(run, "flash_attention_bwd_dq")
