"""The shared expert (4,096 -> 5,376 -> 4,096, ``relu(u)^2``, on every token,
whole on every chip), the expert layers' norm and their sums: device
milliseconds a step, forward, recomputation and backward, of the
instructions traced under the ``moe_shared`` scope
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
    return nemotron_scopes.scope_ms(run, "moe_shared")
