"""AdamW's pass over the parameters and its moments, and the
router-bias update. Device milliseconds a step, forward
and backward, of the instructions traced under the ``update`` scope
(``harness/hlo_scopes.py``: the compiled step's ``op_name``s joined to the
traced slice's ``XLA Ops`` events). ``None`` without a trace, or from a
program whose driver keeps no HLO text."""

from harness import hlo_scopes

LAYER = "optimizer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_tokens",)
CHIPS = None


def read(run):
    return hlo_scopes.step_scope_ms(run, "update")
