"""The ``flash_attention_bwd_dq`` Pallas kernel's share of its roofline in the
SmallThinker cell, in percent (``harness/hlo_scopes.py:kernel_roofline``):
what the mathematics needs, as the mean over the calls a step makes (one
global layer's T(T+1)/2 pairs a head, three window layers' pairs inside the
band; true head width, K and V once a key/value head; ``ops_count/
smallthinker.py``), against the device time of the kernel's events in the
traced slice. The reading of ``flash_attention_bwd_dq_roofline`` under a name
of its own because a reader declares its drivers."""

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
