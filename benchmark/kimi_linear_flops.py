"""Operations and bytes of the Kimi Linear trainer cell, counted from
shapes.

``step_flops``: what one training step's forward and backward require (3x
the forward's multiply-adds at 2 operations each), recomputation not
counted, in ``trainer_flops``'s convention:

  6 x tokens x (matrix parameters every token passes: a KDA layer's q, k,
      v projections and their depthwise convolutions (a weight of the
      [4096, 4] convolution is one multiply-add a token), f_a/f_b,
      g_a/g_b, b_proj and o_proj; an MLA layer's q_proj,
      kv_a_proj_with_mqa, kv_b_proj, o_proj; the dense layer's SwiGLU or
      an expert layer's router and shared expert; lm_head)
+ 6 x routed slots x 3 x hidden x expert width (each (token, slot) pair
      routed to an expert held here passes that expert's three matrices)
+ 3 x causal attention of the MLA layers: per layer and sequence,
      S(S+1)/2 query-key pairs, each 2 x heads x (d_qk + d_v) operations
+ each KDA layer's core, forward and backward (``kda_core_work``)

``kda_core_work``: one KDA layer's chunked gated delta rule over ``seqs``
sequences of ``seq_len`` tokens, at chunks of CHUNK = 64 tokens whatever
the program's own chunk, d = head_dim for keys and values. Per chunk and
head the forward takes, in multiply-adds,

  C(C-1)/2 d   the keys' decayed products (strictly lower)
  C(C+1)/2 d   the queries' decayed products (lower)
  C(C-1)/2 2d  the unit lower-triangular solve, [beta v | beta k e^Gamma]
  3 C d^2      the state read by the keys and by the queries, and written
  C(C+1)/2 d   the output's part within the chunk

at 2 operations each; the backward twice the forward's. Its bytes are
what must cross HBM, fp32: the forward reads q, k, v, g and beta and
writes o; the backward reads those and o's gradient and writes theirs.
The state stays on the chip. A padded last chunk counts as a whole one.
"""

from __future__ import annotations

CHUNK = 64


def _kda(c: dict):
    lin = c["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def kda_layers(c: dict) -> int:
    """Layers kept here whose attention is KDA (1-indexed in the config)."""
    n = c["num_hidden_layers"]
    return sum(1 for i in c["linear_attn_config"]["kda_layers"] if i <= n)


def dense_params_per_token(c: dict) -> int:
    """Matrix parameters every token passes through (experts aside)."""
    h = c["hidden_size"]
    nh = c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    r = c["kv_lora_rank"]
    mla = h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv) + nh * dv * h
    n, d, w = _kda(c)
    kda = 3 * n * d * h + 3 * n * d * w + 2 * (d * h + n * d * d) + n * h \
        + n * d * h
    layers = c["num_hidden_layers"]
    n_kda = kda_layers(c)
    dense = c["first_k_dense_replace"]
    ffn = dense * 3 * h * c["intermediate_size"] + (layers - dense) * (
        c["router_experts"] * h
        + 3 * h * c["num_shared_experts"] * c["moe_intermediate_size"])
    return n_kda * kda + (layers - n_kda) * mla + ffn + c["vocab_size"] * h


def attention_flops(c: dict, seqs: int, seq_len: int) -> int:
    """Forward operations of the MLA layers' causal attention."""
    per_pair = 2 * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    mla_layers = c["num_hidden_layers"] - kda_layers(c)
    return mla_layers * seqs * seq_len * (seq_len + 1) // 2 * per_pair


def kda_core_work(c: dict, seqs: int, seq_len: int):
    """One KDA layer's core: ((forward operations, forward bytes),
    (backward operations, backward bytes))."""
    n, d, _ = _kda(c)
    chunks = seqs * -(-seq_len // CHUNK)
    tri, tri_in = CHUNK * (CHUNK - 1) // 2, CHUNK * (CHUNK + 1) // 2
    macs = tri * d + tri_in * d + tri * 2 * d + 3 * CHUNK * d * d \
        + tri_in * d
    fwd_ops = 2 * macs * chunks * n
    tokens = seqs * seq_len
    qkvg = 4 * tokens * n * d * 4
    beta = tokens * n * 4
    o = tokens * n * d * 4
    return ((fwd_ops, qkvg + beta + o),
            (2 * fwd_ops, 2 * (qkvg + beta) + o))


def step_flops(c: dict, seqs: int, seq_len: int, routed_slots: int) -> int:
    """One step's forward and backward, with ``routed_slots`` (token, slot)
    pairs routed to held experts over all expert layers."""
    tokens = seqs * seq_len
    expert = 3 * c["hidden_size"] * c["moe_intermediate_size"]
    (fwd, _), (bwd, _) = kda_core_work(c, seqs, seq_len)
    return (6 * tokens * dense_params_per_token(c)
            + 6 * routed_slots * expert
            + 3 * attention_flops(c, seqs, seq_len)
            + kda_layers(c) * (fwd + bwd))
