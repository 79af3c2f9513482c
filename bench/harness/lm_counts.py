"""Operations and bytes of a ContAccum update of one shared
Moonlight-16B-A3B tower at one chip's share, from shapes alone: what the
mathematics asks of this chip, not what an implementation does. Absent
experts, recomputation (rematerialized layers, the query side's second
forward), embedding gathers and elementwise work are not counted.

Per token, forward: every layer's latent attention projections (W_q,
W_kv_a, W_kv_b, W_o) and its QK^T and AV products over the full sequence;
the dense layer's SwiGLU; in each expert layer the router, the shared
experts' SwiGLU and the held experts' SwiGLU for the slots routed to them,
on average ``top_k * held / experts`` slots a token (0.75 here). Forward
and backward are three times the forward.
"""

from __future__ import annotations

from bench.harness import counts


def mla_flops_per_token(model: dict, seq_len: int) -> float:
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    r, vd = model["kv_lora_rank"], model["v_head_dim"]
    proj = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) + h * vd * d
    att = seq_len * h * (nope + rope) + seq_len * h * vd
    return 2.0 * (proj + att)


def expert_slots_per_token(model: dict) -> float:
    """Expected slots a token routes to the experts held here."""
    return model["num_experts_per_tok"] * model["n_routed_experts"] / model["router_experts"]


def swiglu_flops(d: int, width: int) -> float:
    return 2.0 * 3 * d * width


def tower_forward_flops(n_seqs: int, seq_len: int, model: dict) -> float:
    d = model["hidden_size"]
    nd = model["first_k_dense_replace"]
    nm = model["num_hidden_layers"] - nd
    f = model["moe_intermediate_size"]
    mla = mla_flops_per_token(model, seq_len)
    dense = mla + swiglu_flops(d, model["intermediate_size"])
    moe = (mla + 2.0 * d * model["router_experts"]
           + swiglu_flops(d, model["n_shared_experts"] * f)
           + expert_slots_per_token(model) * swiglu_flops(d, f))
    return float(n_seqs * seq_len * (nd * dense + nm * moe))


def update_flops(wl: dict, model: dict) -> float:
    """Matrix-product FLOPs of one update, forward and backward: the tower
    over every query and passage of the batch at their lengths, and every
    chunk's similarity matrix once the banks are full."""
    b, k = wl["total_batch"], wl["total_batch"] // wl["local_batch"]
    towers = (tower_forward_flops(b, wl["q_len"], model)
              + tower_forward_flops(b * (1 + wl["n_hard"]), wl["p_len"], model))
    rows, cols = counts.contaccum_chunk_shapes(wl, model["hidden_size"])
    return 3.0 * (towers + k * counts.similarity_flops(rows, cols, model["hidden_size"]))


def expert_work(rows_routed: float, model: dict):
    """(flops, bytes) of the grouped expert products of one update that
    routed ``rows_routed`` slots to the held experts over all its expert
    layers: three products a slot forward (gate, up, down), each with its
    two backward products; the held experts' bf16 weights read once."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    nm = model["num_hidden_layers"] - model["first_k_dense_replace"]
    flops = 3.0 * rows_routed * swiglu_flops(d, f)
    bytes_ = 2.0 * 3 * d * f * model["n_routed_experts"] * nm
    return flops, bytes_
