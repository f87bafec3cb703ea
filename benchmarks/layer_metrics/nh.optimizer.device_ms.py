"""AdamW's pass over the 700.9M parameters and their moments, and the
balancing bias's step: device milliseconds a step of the instructions traced
under the ``update`` scope
(``harness/nemotron_scopes.py``: the compiled step's ``op_name``s joined
to the traced slice's ``XLA Ops`` events, in this cell's own order of
scopes). ``None`` without a trace, or from a program whose driver keeps no
HLO text or that has no such scope."""

from harness import nemotron_scopes

LAYER = "optimizer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_lm",)
CHIPS = None


def read(run):
    return nemotron_scopes.scope_ms(run, "update")
