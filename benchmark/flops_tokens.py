"""Operations and bytes that one training step over patch tokens needs,
counted from the configuration's shapes and from nothing the program reports
(``flops.py``'s part for ``reference_tokens``' model).

A multiply-add is two operations; a product's backward pass costs its forward
twice over (gradient to each operand). Where an operand needs no gradient the
product counts twice: the patch embedding (its input is the image) and the
indexer's three projections (their input is held fixed). Per query ``t`` of a
row of ``T`` tokens: attention's scores and values over the ``min(t + 1,
topk)`` keys it attends to; the indexer's scores over all ``t + 1`` causal
keys forward, and backward over the selected ones only (the KL reaches no
other). The expert products count the assignments that land on the held
experts when the load is balanced: ``per_token * held / n_experts`` a token.
Nothing recomputed is counted, and the elementwise work, the norms, the
rotary embedding, the softmaxes, the selection, the augmentation and the
optimizer are left out: the count is a floor.
"""

from __future__ import annotations

import reference_tokens as reference

BYTES = 4  # float32 activations and weights


def _tokens(a: dict, size: int) -> int:
    return (size // a["patch_size"]) ** 2


def _pairs(tokens: int, topk: int):
    """(causal pairs, attended pairs) of one row."""
    causal = tokens * (tokens + 1) // 2
    if tokens <= topk:
        return causal, causal
    return causal, topk * (topk + 1) // 2 + (tokens - topk) * topk


def held_assignments_per_token(a: dict) -> float:
    return a["num_experts_per_tok"] * a["experts_held"][1] / a["num_experts"]


def layer_macs_per_row(a: dict, tokens: int) -> dict:
    """Multiply-adds of one layer for one row, by part, each as (forward,
    passes): the passes that forward and backward make of it."""
    d, h, g, hd = (a["hidden_size"], a["num_attention_heads"], a["num_key_value_heads"],
                   a["head_dim"])
    j, di, e, f = (a["indexer_num_heads"], a["indexer_head_dim"], a["num_experts"],
                   a["moe_intermediate_size"])
    causal, attended = _pairs(tokens, a["topk"])
    return {
        "projections": (tokens * d * (h * hd + 2 * g * hd) + tokens * h * hd * d, 3),
        "attention": (2 * attended * h * hd, 3),
        "indexer_projections": (tokens * d * (j * di + di + j), 2),
        "indexer_scores_forward": (causal * j * (di + 1), 1),
        "indexer_scores_backward": (attended * j * (di + 1), 2),
        "router": (tokens * d * e, 3),
        "experts": (tokens * held_assignments_per_token(a) * 3 * d * f, 3),
    }


def step_flops(model: str, size: int, global_batch: int, feat_dim: int = 128) -> float:
    """Everything counted for one step at ``global_batch`` images, two views
    each: patch embedding, the layers, the dense head, NT-Xent's similarity
    matrix (one product forward, two backward)."""
    a = reference.arch(model)
    rows, tokens, d = 2 * global_batch, _tokens(a, size), a["hidden_size"]
    per_row = 2 * tokens * a["patch_size"] ** 2 * 3 * d  # embedding: forward, weights
    per_row += a["num_hidden_layers"] * sum(
        macs * passes for macs, passes in layer_macs_per_row(a, tokens).values())
    per_row += 3 * (d * d + d * feat_dim)
    return 2.0 * per_row * rows + 3 * 2 * rows * rows * feat_dim


def flops_per_image(model: str, size: int, global_batch: int, feat_dim: int = 128) -> float:
    return step_flops(model, size, global_batch, feat_dim) / global_batch


def expert_matmul_flops_per_step(model: str, size: int, rows: int) -> float:
    """Forward and backward of the grouped products (gate, up, down) over the
    held assignments of ``rows`` rows, all layers."""
    a = reference.arch(model)
    macs, passes = layer_macs_per_row(a, _tokens(a, size))["experts"]
    return 2.0 * macs * passes * rows * a["num_hidden_layers"]


def expert_matmul_min_bytes_per_step(model: str, size: int, rows: int) -> float:
    """The least traffic the grouped products need: each of a product's three
    passes reads its two operands and writes its result once."""
    a = reference.arch(model)
    d, f, held = a["hidden_size"], a["moe_intermediate_size"], a["experts_held"][1]
    m = rows * _tokens(a, size) * held_assignments_per_token(a)
    one_product = m * d + held * d * f + m * f  # the same three arrays in every pass
    return 3.0 * 3 * one_product * BYTES * a["num_hidden_layers"]


def expert_matmul_min_seconds(model, size, rows, peak_flops, peak_bytes_per_s):
    """The roofline of one step's grouped products on one chip, and which
    side sets it."""
    t_flops = expert_matmul_flops_per_step(model, size, rows) / peak_flops
    t_bytes = expert_matmul_min_bytes_per_step(model, size, rows) / peak_bytes_per_s
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
