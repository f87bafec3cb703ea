"""Latent attention of the main model's blocks: norms, the low-rank
projections, RoPE, the attention kernels, the output projection (the MTP
module's attention is under ``mtp``). Device milliseconds a step, forward
and backward, of the instructions traced under the ``mla`` scope
(``harness/hlo_scopes.py``: the compiled step's ``op_name``s joined to the
traced slice's ``XLA Ops`` events). ``None`` without a trace, or from a
program whose driver keeps no HLO text."""

from harness import hlo_scopes

LAYER = "latent attention"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_tokens",)
CHIPS = None


def read(run):
    return hlo_scopes.step_scope_ms(run, "mla")
