"""The global layer's attention (models/smallthinker.py: the norm, the four
projections, no positions, the flash kernels over the whole prefix, the
output projection): device milliseconds a step, forward, recomputation and
backward, of the instructions traced under the ``attn_full`` scope
(``harness/smallthinker_scopes.py``: the compiled step's ``op_name``s joined
to the traced slice's ``XLA Ops`` events, in this cell's own order of
scopes). ``None`` without a trace, or from a program whose driver keeps no
HLO text."""

from harness import smallthinker_scopes

LAYER = "full attention"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_lm",)
CHIPS = None


def read(run):
    return smallthinker_scopes.scope_ms(run, "attn_full")
