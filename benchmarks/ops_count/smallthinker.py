"""Operations one training sequence of the SmallThinker-21BA3B cut needs, from
the layer shapes, and what its kernels' mathematics needs.

An "image" of this configuration is one packed sequence of
``sequence_length`` tokens. Multiply-accumulates of a forward pass, from
shapes alone: the four attention projections (28 query heads, 4 key/value
heads), the scores over the query-key pairs a layer's mask keeps (T(T+1)/2 a
head on a global layer; on a window layer the pairs *inside the band*, ``W(W
+ 1)/2 + (T - W) W``), q.k and P.v a pair, the router over its published
width, the routed experts at ``experts a token x held / published`` experts
a token (the share of a token's assignments that the experts held here
receive under even routing), and the head over the vocabulary slice. A
training sequence is 3 forward passes of matmul work, 2 operations a
multiply-accumulate. Recomputation, norms, softmax, RoPE, the sort and AdamW
are not counted.
"""

from __future__ import annotations


def pairs_per_head(a: dict, windowed: bool) -> int:
    """Query-key pairs one head of one sequence computes on one layer."""
    t, w = int(a["sequence_length"]), int(a["sliding_window_size"])
    if not windowed or w >= t:
        return t * (t + 1) // 2
    return w * (w + 1) // 2 + (t - w) * w


def _layers(a: dict) -> list[bool]:
    """Whether each layer run here is windowed."""
    layout = [bool(x) for x in a["sliding_window_layout"]]
    assert len(layout) == int(a["layers"])
    return layout


def _attention_macs_per_token(a: dict) -> int:
    d, hd = int(a["hidden_size"]), int(a["head_dim"])
    return (2 * d * int(a["num_attention_heads"]) * hd
            + 2 * d * int(a["num_key_value_heads"]) * hd)


def score_macs(a: dict, windowed: bool) -> int:
    """One layer, one sequence: q.k and P.v over the pairs the mask keeps."""
    return (pairs_per_head(a, windowed) * int(a["num_attention_heads"])
            * 2 * int(a["head_dim"]))


def _expert_layer_macs_per_token(a: dict) -> float:
    d, f = int(a["hidden_size"]), int(a["moe_ffn_hidden_size"])
    e = int(a["moe_num_primary_experts_published"])
    routed = int(a["moe_num_active_primary_experts"]) * int(a["held_experts"]) / e
    return d * e + routed * 3 * d * f


def forward_macs(a: dict) -> float:
    """``a``: the configuration file's ``architecture`` group."""
    t, d = int(a["sequence_length"]), int(a["hidden_size"])
    per_token = (_attention_macs_per_token(a)
                 + _expert_layer_macs_per_token(a))
    return (sum(t * per_token + score_macs(a, w) for w in _layers(a))
            + t * d * int(a["vocab_size"]))


def train_flops_per_image(a: dict) -> float:
    return 2.0 * 3.0 * forward_macs(a)


def parameter_count(a: dict) -> int:
    """Trainable elements held here."""
    d, f = int(a["hidden_size"]), int(a["moe_ffn_hidden_size"])
    layer = (_attention_macs_per_token(a) + 2 * d
             + d * int(a["moe_num_primary_experts_published"])
             + int(a["held_experts"]) * 3 * d * f)
    return int(a["layers"]) * layer + 2 * int(a["vocab_size"]) * d + d


# -- the flash attention kernels: what the mathematics needs ----------------
# One call serves one layer's ``batch`` sequences. A step calls each kernel
# on one global layer and three window layers (the forward twice a layer:
# a block is recomputed), and ``harness/hlo_scopes.py:kernel_roofline``
# multiplies ONE cost by the number of events it finds: so a cost here is
# the MEAN over the layers run. Operations count the pairs the layer's mask
# keeps (inside the band on a window layer) at the true head width. Bytes
# are each operand read once and each result written once in the kernel's
# dtype (bf16, 2 bytes; the float32 row statistics 4), K and V (and dK, dV)
# once a KEY/VALUE head: a group's 7 query heads share them.

def _mean_pairs(a: dict, batch: int) -> float:
    layers = _layers(a)
    return (batch * int(a["num_attention_heads"])
            * sum(pairs_per_head(a, w) for w in layers) / len(layers))


def _rows(a: dict, batch: int) -> tuple[int, int]:
    """(query-head rows, key/value-head rows) of one call."""
    t = int(a["sequence_length"])
    return (batch * int(a["num_attention_heads"]) * t,
            batch * int(a["num_key_value_heads"]) * t)


def flash_attention_fwd_cost(a: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes): s = q.k and o = p.v; reads q, k, v, writes o and
    the log-sum-exp."""
    hd, (q_rows, kv_rows) = int(a["head_dim"]), _rows(a, batch)
    return (2.0 * _mean_pairs(a, batch) * 2 * hd,
            q_rows * (2.0 * 2 * hd + 4) + kv_rows * 2.0 * 2 * hd)


def flash_attention_bwd_dq_cost(a: dict, batch: int) -> tuple[float, float]:
    """s = q.k again, dp = do.v, dq = ds.k; reads q, k, v, do and the two
    row statistics, writes dq."""
    hd, (q_rows, kv_rows) = int(a["head_dim"]), _rows(a, batch)
    return (2.0 * _mean_pairs(a, batch) * 3 * hd,
            q_rows * (2.0 * 3 * hd + 8) + kv_rows * 2.0 * 2 * hd)


def flash_attention_bwd_dkv_cost(a: dict, batch: int) -> tuple[float, float]:
    """s again, dv = p.do, dp = do.v, dk = ds.q; reads q, k, v, do and the
    row statistics, writes dk and dv."""
    hd, (q_rows, kv_rows) = int(a["head_dim"]), _rows(a, batch)
    return (2.0 * _mean_pairs(a, batch) * 4 * hd,
            q_rows * (2.0 * 2 * hd + 8) + kv_rows * 2.0 * 4 * hd)
