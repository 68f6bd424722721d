"""Operations of the trainer cell, counted from shapes.

``step_flops``: what one training step's forward and backward require (3x
the forward's multiply-adds at 2 operations each), recomputation not
counted, as model FLOP/s utilization counts them:

  6 x tokens x (matrix parameters every token passes: each layer's
      q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj, the dense layer's
      SwiGLU or an expert layer's router and shared experts, lm_head)
+ 6 x routed slots x 3 x hidden x expert width (each (token, slot) pair
      routed to an expert held here passes that expert's three matrices)
+ 3 x causal attention: per layer and sequence, S(S+1)/2 query-key pairs,
      each 2 x heads x (d_qk + d_v) operations forward

The embedding is a gather and the norms are vectors: neither counted.

``grouped_matmul_flops``: one grouped-matmul op of the expert layer
(``jax.lax.ragged_dot``, in the device trace), 2 x rows x k x n, where
[groups, k, n] is the op's own 3-D operand or result (the stacked expert
weights or their gradient) and ``rows`` the (token, slot) pairs routed to
the held experts. Rows cannot come from the op's shapes: the dropless
buffer is sized for every slot and the op skips the rows past its groups.
"""

from __future__ import annotations

import math
import re
from typing import Optional

_SHAPE = re.compile(r"[a-z0-9]+\[([0-9,]*)\]")


def dense_params_per_token(c: dict) -> int:
    """Matrix parameters every token passes through (experts aside)."""
    h, nh = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    r = c["kv_lora_rank"]
    attn = h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv) \
        + nh * dv * h
    dense = c["first_k_dense_replace"]
    moe = c["num_hidden_layers"] - dense
    ffn = dense * 3 * h * c["intermediate_size"] + moe * (
        c["router_experts"] * h
        + 3 * h * c["n_shared_experts"] * c["moe_intermediate_size"])
    return c["num_hidden_layers"] * attn + ffn + c["vocab_size"] * h


def attention_flops(c: dict, seqs: int, seq_len: int) -> int:
    """Forward operations of causal attention over the whole stack."""
    per_pair = 2 * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    return (c["num_hidden_layers"] * seqs * seq_len * (seq_len + 1) // 2
            * per_pair)


def step_flops(c: dict, seqs: int, seq_len: int, routed_slots: int) -> int:
    """One step's forward and backward, with ``routed_slots`` (token, slot)
    pairs routed to held experts over all expert layers."""
    tokens = seqs * seq_len
    expert = 3 * c["hidden_size"] * c["moe_intermediate_size"]
    return (6 * tokens * dense_params_per_token(c)
            + 6 * routed_slots * expert
            + 3 * attention_flops(c, seqs, seq_len))


def grouped_matmul_flops(op_text: str, rows: float) -> Optional[float]:
    """2 x rows x k x n of one grouped-matmul op; None for any other op.
    On a TPU each ragged dot is a kernel of its own, named
    ``ragged-dot-<n>`` (beside a ``ragged-dot-metadata`` op that counts
    the groups' tiles); elsewhere it is a ``ragged-dot`` instruction."""
    head, _, rest = op_text.partition(" = ")
    name = head.lstrip("%").strip()
    if name.startswith("ragged-dot-metadata") or not (
            name.startswith("ragged-dot") or " ragged-dot(" in rest):
        return None
    for m in _SHAPE.finditer(rest):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if len(dims) == 3:
            return 2.0 * rows * math.prod(dims[1:])
    return None
