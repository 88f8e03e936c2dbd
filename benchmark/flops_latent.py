"""Operations and bytes that one training step over patch tokens needs
through Moonlight's block, counted from the configuration's shapes and from
nothing the program reports (``flops_tokens.py``'s part for
``reference_latent``'s model).

A multiply-add is two operations; a product's backward pass costs its forward
twice over (gradient to each operand). The patch embedding counts twice: its
input is the image, which needs no gradient. Per row of ``T`` tokens a layer
has the latent attention's four projections (``W_q``, ``W_kva``, ``W_kvb``,
``W_o``) and its core (scores and values over the ``T (T + 1) / 2`` causal
pairs, in every head ``qk_nope_head_dim + qk_rope_head_dim`` multiply-adds a
score and ``v_head_dim`` a value), and then either the dense MLP (the first
``first_k_dense_replace`` layers) or the router, the shared experts and the
routed ones. The routed products count the assignments that land on the held
experts when the load is balanced: ``per_token * held / n_experts`` a token.
Nothing recomputed is counted, and the elementwise work, the norms, the
rotary embedding, the softmaxes, the top-k, the augmentation and the
optimizer are left out: the count is a floor.
"""

from __future__ import annotations

import reference_latent as reference

BYTES = 4  # float32 activations and weights


def _tokens(a: dict, size: int) -> int:
    return (size // a["patch_size"]) ** 2


def _expert_layers(a: dict) -> int:
    return a["num_hidden_layers"] - a["first_k_dense_replace"]


def held_assignments_per_token(a: dict) -> float:
    return a["num_experts_per_tok"] * a["experts_held"][1] / a["num_experts"]


def attention_macs_per_row(a: dict, tokens: int) -> dict:
    """Multiply-adds of one layer's attention for one row, forward."""
    d, h, r = a["hidden_size"], a["num_attention_heads"], a["kv_lora_rank"]
    dn, dr, dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    return {
        "projections": tokens * (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
                                 + h * dv * d),
        "attn_core": tokens * (tokens + 1) // 2 * h * (dn + dr + dv),
    }


def feed_forward_macs_per_row(a: dict, tokens: int) -> dict:
    """Multiply-adds of one layer's feed-forward part for one row, forward:
    ``dense`` is a leading layer's, the others an expert layer's."""
    d = a["hidden_size"]
    return {
        "dense": tokens * 3 * d * a["intermediate_size"],
        "router": tokens * d * a["num_experts"],
        "shared": tokens * 3 * d * a["shared_intermediate_size"],
        "experts": tokens * held_assignments_per_token(a) * 3 * d * a["moe_intermediate_size"],
    }


def step_flops(model: str, size: int, global_batch: int, feat_dim: int = 128) -> float:
    """Everything counted for one step at ``global_batch`` images, two views
    each: patch embedding, the layers, the dense head, NT-Xent's similarity
    matrix (one product forward, two backward)."""
    a = reference.arch(model)
    rows, tokens, d = 2 * global_batch, _tokens(a, size), a["hidden_size"]
    attention, ff = attention_macs_per_row(a, tokens), feed_forward_macs_per_row(a, tokens)
    per_row = 2 * tokens * a["patch_size"] ** 2 * 3 * d  # embedding: forward, weights
    per_row += 3 * a["num_hidden_layers"] * sum(attention.values())
    per_row += 3 * a["first_k_dense_replace"] * ff["dense"]
    per_row += 3 * _expert_layers(a) * (ff["router"] + ff["shared"] + ff["experts"])
    per_row += 3 * (d * d + d * feat_dim)
    return 2.0 * per_row * rows + 3 * 2 * rows * rows * feat_dim


def flops_per_image(model: str, size: int, global_batch: int, feat_dim: int = 128) -> float:
    return step_flops(model, size, global_batch, feat_dim) / global_batch


def expert_matmul_flops_per_step(model: str, size: int, rows: int) -> float:
    """Forward and backward of the grouped products (gate, up, down) over the
    held assignments of ``rows`` rows, all expert layers."""
    a = reference.arch(model)
    macs = feed_forward_macs_per_row(a, _tokens(a, size))["experts"]
    return 2.0 * macs * 3 * rows * _expert_layers(a)


def expert_matmul_min_bytes_per_step(model: str, size: int, rows: int) -> float:
    """The least traffic the grouped products need: each of a product's three
    passes reads its two operands and writes its result once."""
    a = reference.arch(model)
    d, f, held = a["hidden_size"], a["moe_intermediate_size"], a["experts_held"][1]
    m = rows * _tokens(a, size) * held_assignments_per_token(a)
    one_product = m * d + held * d * f + m * f  # the same three arrays in every pass
    return 3.0 * 3 * one_product * BYTES * _expert_layers(a)


def expert_matmul_min_seconds(model, size, rows, peak_flops, peak_bytes_per_s):
    """The roofline of one step's grouped products on one chip, and which
    side sets it (``flops_tokens.expert_matmul_min_seconds``'s signature)."""
    t_flops = expert_matmul_flops_per_step(model, size, rows) / peak_flops
    t_bytes = expert_matmul_min_bytes_per_step(model, size, rows) / peak_bytes_per_s
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")


def attn_core_flops_per_step(model: str, size: int, rows: int) -> float:
    """Forward and backward of attention's scores and values over the causal
    pairs of ``rows`` rows, all layers."""
    a = reference.arch(model)
    macs = attention_macs_per_row(a, _tokens(a, size))["attn_core"]
    return 2.0 * macs * 3 * rows * a["num_hidden_layers"]


def attn_core_min_bytes_per_step(model: str, size: int, rows: int) -> float:
    """The least traffic scores and values need, whatever computes them: the
    forward reads ``q``, ``k``, ``v`` and writes ``o``; the backward reads
    those four and ``do`` and writes ``dq``, ``dk``, ``dv``. No score goes
    through memory."""
    a = reference.arch(model)
    h, dqk, dv = (a["num_attention_heads"], a["qk_nope_head_dim"] + a["qk_rope_head_dim"],
                  a["v_head_dim"])
    qk, v = _tokens(a, size) * h * dqk, _tokens(a, size) * h * dv
    forward, backward = 2 * qk + 2 * v, (2 * qk + 3 * v) + (2 * qk + v)
    return float(forward + backward) * BYTES * rows * a["num_hidden_layers"]


def attn_core_min_seconds(model, size, rows, peak_flops, peak_bytes_per_s):
    """The roofline of one step's scores and values on one chip, and which
    side sets it."""
    t_flops = attn_core_flops_per_step(model, size, rows) / peak_flops
    t_bytes = attn_core_min_bytes_per_step(model, size, rows) / peak_bytes_per_s
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
