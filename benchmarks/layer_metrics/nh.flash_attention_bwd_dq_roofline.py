"""The ``flash_attention_bwd_dq`` Pallas kernel's share of its roofline in the
Nemotron cell, in percent (``harness/hlo_scopes.py:kernel_roofline``): what
the mathematics needs of a call (one attention layer, 8,192 causal tokens,
T(T+1)/2 pairs a head, 4 query heads on one key/value head of 128, K and V
once a key/value head; ``ops_count/nemotron_h.py``), against the device time
of the kernel's events in the traced slice. The reading of
``flash_attention_bwd_dq_roofline`` under a name of its own because a reader
declares its drivers."""

from harness import hlo_scopes

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "mfu"
DRIVERS = ("sync_mesh_lm",)
CHIPS = None


def read(run):
    return hlo_scopes.kernel_roofline(run, "flash_attention_bwd_dq")
