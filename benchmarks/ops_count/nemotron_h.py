"""Operations one training sequence of the Nemotron-3-Super cut needs, from
the layer shapes, and what its kernels' and its scan's mathematics needs.

An "image" of this configuration is one packed sequence of
``sequence_length`` tokens. Multiply-accumulates of a forward pass, from
shapes alone, a layer of ``pattern`` at a time:

- ``M``: the in-projection (``z``, ``x``, ``B``, ``C``, ``dt`` of the heads
  and groups held) and the out-projection, and the scan in its chunked
  (state-space-duality) form, the form whose work is matmuls: inside a chunk
  of ``L`` the ``C . B`` products and their products with ``x`` over the
  pairs the causal mask keeps (``L (L + 1) / 2`` a chunk), a chunk's state
  and its read-out (``P N`` a head a token each);
- ``*``: the four projections and q.k, P.v over the ``T (T + 1) / 2`` pairs a
  head;
- ``E``: the router over its published width, the two latent projections,
  the shared expert, and the routed experts at ``experts a token x held /
  published`` experts a token (the share of a token's assignments that the
  experts held here receive under even routing);

and the head over the vocabulary slice. A training sequence is 3 forward
passes of matmul work, 2 operations a multiply-accumulate. Recomputation,
norms, the convolution, the decays, softmax, the sort and AdamW are not
counted.
"""

from __future__ import annotations


def _mamba(a: dict) -> tuple[int, int, int, int, int]:
    """(heads, head width, groups, state, chunk) held here."""
    return (int(a["mamba_num_heads"]), int(a["mamba_head_dim"]),
            int(a["n_groups"]), int(a["ssm_state_size"]),
            int(a["chunk_size"]))


def _mamba_projection_macs_per_token(a: dict) -> int:
    d = int(a["hidden_size"])
    h, p, g, n, _chunk = _mamba(a)
    return d * (2 * h * p + 2 * g * n + h) + h * p * d


def ssm_scan_macs(a: dict) -> float:
    """One Mamba-2 layer's scan over one sequence, forward."""
    h, p, g, n, chunk = _mamba(a)
    inside = (chunk + 1) / 2 * (g * n + h * p)      # C.B, then (..) x
    return int(a["sequence_length"]) * (inside + 2 * h * p * n)


def _attention_macs_per_token(a: dict) -> int:
    d, hd = int(a["hidden_size"]), int(a["head_dim"])
    return (2 * d * int(a["num_attention_heads"]) * hd
            + 2 * d * int(a["num_key_value_heads"]) * hd)


def pairs_per_head(a: dict) -> int:
    t = int(a["sequence_length"])
    return t * (t + 1) // 2


def score_macs(a: dict) -> int:
    """One attention layer, one sequence: q.k and P.v over the causal
    pairs."""
    return (pairs_per_head(a) * int(a["num_attention_heads"]) * 2
            * int(a["head_dim"]))


def _expert_layer_macs_per_token(a: dict) -> float:
    d, latent = int(a["hidden_size"]), int(a["moe_latent_size"])
    e = int(a["n_routed_experts_published"])
    routed = int(a["num_experts_per_tok"]) * int(a["held_experts"]) / e
    return (d * e + 2 * d * latent
            + 2 * d * int(a["moe_shared_expert_intermediate_size"])
            + routed * 2 * latent * int(a["moe_intermediate_size"]))


def _count(a: dict) -> dict:
    pattern = a["pattern"]
    assert len(pattern) == int(a["layers"]) and set(pattern) <= set("M*E")
    return {kind: pattern.count(kind) for kind in "M*E"}


def forward_macs(a: dict) -> float:
    """``a``: the configuration file's ``architecture`` group."""
    t, d, n = int(a["sequence_length"]), int(a["hidden_size"]), _count(a)
    return (n["M"] * (t * _mamba_projection_macs_per_token(a)
                      + ssm_scan_macs(a))
            + n["*"] * (t * _attention_macs_per_token(a) + score_macs(a))
            + n["E"] * t * _expert_layer_macs_per_token(a)
            + t * d * int(a["vocab_size"]))


def train_flops_per_image(a: dict) -> float:
    return 2.0 * 3.0 * forward_macs(a)


def parameter_count(a: dict) -> int:
    """Trainable elements held here."""
    d, n = int(a["hidden_size"]), _count(a)
    h, p, g, state, _chunk = _mamba(a)
    conv = h * p + 2 * g * state
    mamba = (_mamba_projection_macs_per_token(a)
             + (int(a["conv_kernel"]) + 1) * conv   # the kernel and its bias
             + 3 * h + h * p + d)       # dt_bias, A_log, D; the two norms
    latent, f = int(a["moe_latent_size"]), int(a["moe_intermediate_size"])
    experts = (d * int(a["n_routed_experts_published"]) + 2 * d * latent
               + 2 * d * int(a["moe_shared_expert_intermediate_size"])
               + int(a["held_experts"]) * 2 * latent * f + d)
    return (n["M"] * mamba + n["*"] * (_attention_macs_per_token(a) + d)
            + n["E"] * experts + 2 * int(a["vocab_size"]) * d + d)


# -- the scan: what the mathematics needs of a whole step -------------------
# ``nh.ssm_scan_roofline`` holds the device time under the ``ssm_scan`` scope
# to this, whatever implements the scan: every Mamba-2 layer's scan of
# ``batch`` sequences once forward and once backward (a backward pass is two
# forward passes of matmul work; the forward pass that a recomputed layer
# runs again is not needed and not counted). Bytes are each operand read and
# each result written once: forward reads x, B, C (the kernel's dtype, bf16)
# and dt (float32) and writes y; backward reads those and dy and writes dx,
# dB, dC and d dt: twice the forward's.

def ssm_scan_cost(a: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes) of a step's scans."""
    h, p, g, n, _chunk = _mamba(a)
    t, layers = int(a["sequence_length"]), _count(a)["M"]
    forward_bytes = t * (2 * h * p * 2 + 2 * g * n * 2 + h * 4)
    return (layers * batch * 2.0 * 3.0 * ssm_scan_macs(a),
            layers * batch * 3.0 * forward_bytes)


# -- the flash attention kernels: what the mathematics needs ----------------
# One call serves the one attention layer's ``batch`` sequences (the forward
# kernel is called twice a step: the layer is recomputed; ``harness/
# hlo_scopes.py:kernel_roofline`` multiplies one call's cost by the events
# it finds). Operations count the causal pairs at the true head width; bytes
# are each operand read once and each result written once in the kernel's
# dtype (bf16, 2 bytes; the float32 row statistics 4), K and V (and dK, dV)
# once a KEY/VALUE head: its 4 query heads share them.

def _rows(a: dict, batch: int) -> tuple[int, int]:
    """(query-head rows, key/value-head rows) of one call."""
    t = int(a["sequence_length"])
    return (batch * int(a["num_attention_heads"]) * t,
            batch * int(a["num_key_value_heads"]) * t)


def _pairs(a: dict, batch: int) -> int:
    return batch * int(a["num_attention_heads"]) * pairs_per_head(a)


def flash_attention_fwd_cost(a: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes): s = q.k and o = p.v; reads q, k, v, writes o and
    the log-sum-exp."""
    hd, (q_rows, kv_rows) = int(a["head_dim"]), _rows(a, batch)
    return (2.0 * _pairs(a, batch) * 2 * hd,
            q_rows * (2.0 * 2 * hd + 4) + kv_rows * 2.0 * 2 * hd)


def flash_attention_bwd_dq_cost(a: dict, batch: int) -> tuple[float, float]:
    """s = q.k again, dp = do.v, dq = ds.k; reads q, k, v, do and the two
    row statistics, writes dq."""
    hd, (q_rows, kv_rows) = int(a["head_dim"]), _rows(a, batch)
    return (2.0 * _pairs(a, batch) * 3 * hd,
            q_rows * (2.0 * 3 * hd + 8) + kv_rows * 2.0 * 2 * hd)


def flash_attention_bwd_dkv_cost(a: dict, batch: int) -> tuple[float, float]:
    """s again, dv = p.do, dp = do.v, dk = ds.q; reads q, k, v, do and the
    row statistics, writes dk and dv."""
    hd, (q_rows, kv_rows) = int(a["head_dim"]), _rows(a, batch)
    return (2.0 * _pairs(a, batch) * 4 * hd,
            q_rows * (2.0 * 2 * hd + 8) + kv_rows * 2.0 * 4 * hd)
