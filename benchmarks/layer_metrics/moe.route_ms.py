"""The expert layers' routing: router, top-k, the sort by expert, the
gather of each pass's rows and the scatter back. Device milliseconds a step, forward
and backward, of the instructions traced under the ``moe_route`` scope
(``harness/hlo_scopes.py``: the compiled step's ``op_name``s joined to the
traced slice's ``XLA Ops`` events). ``None`` without a trace, or from a
program whose driver keeps no HLO text."""

from harness import hlo_scopes

LAYER = "expert layer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_tokens",)
CHIPS = None


def read(run):
    return hlo_scopes.step_scope_ms(run, "moe_route")
