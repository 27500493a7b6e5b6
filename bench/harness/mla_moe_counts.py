"""Operations each piece of a latent-attention, routed-expert encoder's work
needs (``mla_moe`` configurations), from real sizes only: real tokens at
each row's real length, and the held experts' work from the choices the
program reports having computed.  PAD tokens, padded rows and choices for
experts held elsewhere are never counted."""

from __future__ import annotations


def _swiglu(d: int, f: int) -> float:
    return 2.0 * 3 * d * f


def row_flops(enc: dict, tokens: int) -> float:
    """Forward FLOPs of one row of ``tokens`` real tokens, every layer but
    the held experts: the latent attention's four projections (``wq``,
    ``wkv_a``, ``wkv_b``, ``wo``), causal attention over each token's
    prefix (scores at the query-key width, values at the value width), the
    leading dense SwiGLU, and each MoE layer's router and shared experts."""
    a = enc["arch_keys"]
    d, heads, layers = enc["d_model"], enc["n_heads"], enc["n_layers"]
    rank, nope, rope, vd = (a["kv_lora_rank"], a["qk_nope_head_dim"],
                            a["qk_rope_head_dim"], a["v_head_dim"])
    proj = 2.0 * (d * heads * (nope + rope) + d * (rank + rope)
                  + rank * heads * (nope + vd) + heads * vd * d)
    pairs = tokens * (tokens + 1) / 2
    attn = 2.0 * heads * (nope + rope + vd) * pairs
    n_moe = layers - a["first_k_dense"]
    per_token = (layers * proj + a["first_k_dense"] * _swiglu(d, enc["d_ff"])
                 + n_moe * (_swiglu(d, a["d_ff_shared"])
                            + 2.0 * d * a["num_experts"]))
    return float(tokens * per_token + layers * attn)


def held_expert_flops(enc: dict, held_choices: int) -> float:
    """FLOPs of ``held_choices`` token-choices computed by held experts
    (each one expert SwiGLU of ``d_ff_expert``)."""
    a = enc["arch_keys"]
    return float(held_choices) * _swiglu(enc["d_model"], a["d_ff_expert"])
