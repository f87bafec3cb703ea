"""Operations one training sequence of the JoyAI-LLM-Flash cut needs, from
the layer shapes, and what its kernels' mathematics needs.

An "image" of this configuration is one packed sequence of
``sequence_length`` tokens. Multiply-accumulates of a forward pass, from
shapes alone: the latent-attention projections, the causal scores counted
as T(T+1)/2 query-key pairs a head (q.k over the q/k width, P.v over the v
width), the dense SwiGLU, the router over its published width, the shared
expert, the routed experts at ``num_experts_per_tok x held / published``
experts a token (the share of a token's assignments that the experts held
here receive under even routing), both heads over the vocabulary slice, and
the multi-token-prediction module (its projection and its block). A training
sequence is 3 forward passes of matmul work, 2 operations a
multiply-accumulate. Recomputation, norms, softmax, RoPE, the sort and
AdamW are not counted.
"""

from __future__ import annotations


def _mla_macs_per_token(a: dict) -> int:
    d, h = int(a["hidden_size"]), int(a["num_attention_heads"])
    qk = int(a["qk_nope_head_dim"]) + int(a["qk_rope_head_dim"])
    return (d * int(a["q_lora_rank"]) + int(a["q_lora_rank"]) * h * qk
            + d * (int(a["kv_lora_rank"]) + int(a["qk_rope_head_dim"]))
            + int(a["kv_lora_rank"]) * h * (int(a["qk_nope_head_dim"])
                                            + int(a["v_head_dim"]))
            + h * int(a["v_head_dim"]) * d)


def causal_score_macs(a: dict) -> int:
    """One layer, one sequence: T(T+1)/2 pairs a head, q.k and P.v."""
    t, h = int(a["sequence_length"]), int(a["num_attention_heads"])
    qk = int(a["qk_nope_head_dim"]) + int(a["qk_rope_head_dim"])
    return t * (t + 1) // 2 * h * (qk + int(a["v_head_dim"]))


def _expert_layer_macs_per_token(a: dict) -> float:
    d, f = int(a["hidden_size"]), int(a["moe_intermediate_size"])
    routed = (int(a["num_experts_per_tok"]) * int(a["held_experts"])
              / int(a["n_routed_experts_published"]))
    return (d * int(a["n_routed_experts_published"])
            + (int(a["n_shared_experts"]) + routed) * 3 * d * f)


def forward_macs(a: dict) -> float:
    """``a``: the configuration file's ``architecture`` group."""
    t, d = int(a["sequence_length"]), int(a["hidden_size"])
    attention = t * _mla_macs_per_token(a) + causal_score_macs(a)
    dense = attention + t * 3 * d * int(a["intermediate_size"])
    expert = attention + t * _expert_layer_macs_per_token(a)
    mtp = int(a["mtp_modules"]) * (t * 2 * d * d + expert)
    heads = (1 + int(a["mtp_modules"])) * t * d * int(a["vocab_size"])
    return (int(a["dense_layers"]) * dense
            + int(a["expert_layers"]) * expert + mtp + heads)


def train_flops_per_image(a: dict) -> float:
    return 2.0 * 3.0 * forward_macs(a)


def parameter_count(a: dict) -> int:
    """Trainable elements held here, the router's balancing bias counted
    with its router as the source counts it."""
    d, h = int(a["hidden_size"]), int(a["num_attention_heads"])
    mla = (_mla_macs_per_token(a) + int(a["q_lora_rank"])
           + int(a["kv_lora_rank"]))                  # the two latent norms
    swiglu = 3 * d * int(a["moe_intermediate_size"])
    e = int(a["n_routed_experts_published"])
    expert_layer = (mla + 2 * d + d * e + e
                    + (int(a["held_experts"])
                       + int(a["n_shared_experts"])) * swiglu)
    dense_layer = mla + 2 * d + 3 * d * int(a["intermediate_size"])
    mtp = int(a["mtp_modules"]) * (2 * d + 2 * d * d + expert_layer + d)
    return (int(a["dense_layers"]) * dense_layer
            + int(a["expert_layers"]) * expert_layer + mtp
            + 2 * int(a["vocab_size"]) * d + d)


# -- the flash attention kernels: what the mathematics needs ----------------
# One call serves one layer's ``batch`` sequences: B*H programs of T
# queries. Operations count the causal half alone (T(T+1)/2 pairs) at the
# true head widths; a kernel that also computes masked pairs or padded
# lanes reads lower for it. Bytes are each operand read once and each
# result written once in the kernel's dtype (bf16, 2 bytes; the float32
# row statistics 4).

def _pairs(a: dict, batch: int) -> int:
    t = int(a["sequence_length"])
    return batch * int(a["num_attention_heads"]) * t * (t + 1) // 2


def _rows(a: dict, batch: int) -> int:
    return batch * int(a["num_attention_heads"]) * int(a["sequence_length"])


def _widths(a: dict) -> tuple[int, int]:
    return (int(a["qk_nope_head_dim"]) + int(a["qk_rope_head_dim"]),
            int(a["v_head_dim"]))


def flash_attention_fwd_cost(a: dict, batch: int) -> tuple[float, float]:
    """(operations, bytes): s = q.k and o = p.v; reads q, k, v, writes o and
    the log-sum-exp."""
    qk, v = _widths(a)
    return (2.0 * _pairs(a, batch) * (qk + v),
            _rows(a, batch) * (2.0 * (2 * qk + 2 * v) + 4))


def flash_attention_bwd_dq_cost(a: dict, batch: int) -> tuple[float, float]:
    """s = q.k again, dp = do.v, dq = ds.k; reads q, k, v, do and the two
    row statistics, writes dq."""
    qk, v = _widths(a)
    return (2.0 * _pairs(a, batch) * (2 * qk + v),
            _rows(a, batch) * (2.0 * (3 * qk + 2 * v) + 8))


def flash_attention_bwd_dkv_cost(a: dict, batch: int) -> tuple[float, float]:
    """s again, dv = p.do, dp = do.v, dk = ds.q; reads q, k, v, do and the
    row statistics, writes dk and dv."""
    qk, v = _widths(a)
    return (2.0 * _pairs(a, batch) * (2 * qk + 2 * v),
            _rows(a, batch) * (2.0 * (3 * qk + 3 * v) + 8))
