"""The attention layer (models/nemotron_h.py: the norm, the four projections,
no positions, the flash kernels over the whole prefix, 4 query heads on one
key/value head, the output projection, the residual): device milliseconds a
step, forward, recomputation and backward, of the instructions traced under
the ``attn_full`` scope
(``harness/nemotron_scopes.py``: the compiled step's ``op_name``s joined
to the traced slice's ``XLA Ops`` events, in this cell's own order of
scopes). ``None`` without a trace, or from a program whose driver keeps no
HLO text or that has no such scope."""

from harness import nemotron_scopes

LAYER = "full attention"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_lm",)
CHIPS = None


def read(run):
    return nemotron_scopes.scope_ms(run, "attn_full")
