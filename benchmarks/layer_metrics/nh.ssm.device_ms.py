"""The five Mamba-2 layers whole (models/nemotron_h.py: the norm, the
in-projection, the convolution, the scan, the gate and its group norm, the
out-projection, the residual): device milliseconds a step, forward,
recomputation and backward, of the instructions traced under the ``ssm``
scope and the ``ssm_scan`` scope inside it
(``harness/nemotron_scopes.py``: the compiled step's ``op_name``s joined
to the traced slice's ``XLA Ops`` events, in this cell's own order of
scopes). ``None`` without a trace, or from a program whose driver keeps no
HLO text or that has no such scope."""

from harness import nemotron_scopes

LAYER = "state-space layers"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_lm",)
CHIPS = None


def read(run):
    return nemotron_scopes.scope_ms(run, "ssm", "ssm_scan")
