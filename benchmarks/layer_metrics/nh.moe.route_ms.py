"""The router, the sort of 360,448 assignments a layer, the passes' gathers and
the way back to the tokens' sums (XLA's ``sort`` and ``scatter`` lose their
path and go by instruction name): device milliseconds a step, forward,
recomputation and backward, of the instructions traced under the
``moe_route`` scope
(``harness/nemotron_scopes.py``: the compiled step's ``op_name``s joined
to the traced slice's ``XLA Ops`` events, in this cell's own order of
scopes). ``None`` without a trace, or from a program whose driver keeps no
HLO text or that has no such scope."""

from harness import nemotron_scopes

LAYER = "expert layer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_lm",)
CHIPS = None


def read(run):
    return nemotron_scopes.scope_ms(run, "moe_route")
