"""Operations and bytes that one training step over patch tokens needs
through Qwen3-Next's block, counted from the configuration's shapes and from
nothing the program reports (``flops_latent.py``'s part for
``reference_delta``'s model).

A multiply-add is two operations; a product's backward pass costs its forward
twice over (gradient to each operand). The patch embedding counts twice: its
input is the image, which needs no gradient. Per row of ``T`` tokens a layer
has its mixer and its expert layer. A Gated DeltaNet mixer: ``W_qkvz``,
``W_ba``, ``W_out`` and the chunked delta rule (``delta_scan_macs_per_chunk``,
a chunk of ``chunk_size`` tokens a value head). A gated-attention mixer:
``W_q`` (queries and gates), ``W_k``, ``W_v``, ``W_o`` and scores and values
over the ``T (T + 1) / 2`` causal pairs, ``head_dim`` multiply-adds each in
every head. The expert layer: router, the shared expert and its gate, and the
routed products over the assignments that land on the held experts when the
load is balanced (``per_token * held / n_experts`` a token). Nothing
recomputed is counted, and the elementwise work (the convolution, the
norms, the gates, the rotary embedding, the softmaxes, the top-k), the
augmentation and the optimizer are left out: the count is a floor.
"""

from __future__ import annotations

import reference_delta as reference

BYTES = 4  # float32 activations and weights


def _tokens(a: dict, size: int) -> int:
    return (size // a["patch_size"]) ** 2


def _layers_of_each(a: dict) -> tuple:
    """(Gated DeltaNet layers, full-attention layers)."""
    full = sum(reference.is_full(a, i) for i in range(a["num_hidden_layers"]))
    return a["num_hidden_layers"] - full, full


def delta_scan_macs_per_chunk(a: dict) -> int:
    """Multiply-adds of the chunked delta rule for one chunk of ``C`` tokens
    and one value head, forward: ``k k^T`` over the pairs below the diagonal
    and ``q k^T`` over those on and below it; ``(I + N)^-1`` by forward
    substitution; ``W = T (B e^G K)`` and ``U' = T B V`` with ``T`` lower
    triangular; ``W S``, ``e^G Q S`` and ``(e^(G_C - G) K)^T U`` against the
    ``dk x dv`` state; ``P U`` over the causal pairs."""
    c, dk, dv = a["chunk_size"], a["linear_key_head_dim"], a["linear_value_head_dim"]
    below, causal = c * (c - 1) // 2, c * (c + 1) // 2
    return (below * dk + causal * dk + c * (c - 1) * (c - 2) // 6
            + causal * (dk + dv) + 3 * c * dk * dv + causal * dv)


def mixer_macs_per_row(a: dict, tokens: int) -> dict:
    """Multiply-adds of one layer's mixer for one row, forward, of each kind."""
    d = a["hidden_size"]
    hk, hv, dk, dv = (a["linear_num_key_heads"], a["linear_num_value_heads"],
                      a["linear_key_head_dim"], a["linear_value_head_dim"])
    heads, kv, hd = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    return {
        "linear_projections": tokens * d * (2 * hk * dk + 2 * hv * dv + 2 * hv + hv * dv),
        "delta_scan": tokens // a["chunk_size"] * hv * delta_scan_macs_per_chunk(a),
        "full_projections": tokens * d * (heads * 2 * hd + 2 * kv * hd + heads * hd),
        "attn_core": tokens * (tokens + 1) // 2 * heads * 2 * hd,
    }


def held_assignments_per_token(a: dict) -> float:
    return a["num_experts_per_tok"] * a["experts_held"][1] / a["num_experts"]


def expert_layer_macs_per_row(a: dict, tokens: int) -> dict:
    """Multiply-adds of one expert layer for one row, forward: the shared
    expert with its gate's ``[D, 1]`` product."""
    d = a["hidden_size"]
    return {
        "router": tokens * d * a["num_experts"],
        "shared": tokens * d * (3 * a["shared_expert_intermediate_size"] + 1),
        "experts": tokens * held_assignments_per_token(a) * 3 * d * a["moe_intermediate_size"],
    }


def step_flops(model: str, size: int, global_batch: int, feat_dim: int = 128) -> float:
    """Everything counted for one step at ``global_batch`` images, two views
    each: patch embedding, the layers, the dense head, NT-Xent's similarity
    matrix (one product forward, two backward)."""
    a = reference.arch(model)
    rows, tokens, d = 2 * global_batch, _tokens(a, size), a["hidden_size"]
    linear, full = _layers_of_each(a)
    mixer, moe = mixer_macs_per_row(a, tokens), expert_layer_macs_per_row(a, tokens)
    per_row = 2 * tokens * a["patch_size"] ** 2 * 3 * d  # embedding: forward, weights
    per_row += 3 * linear * (mixer["linear_projections"] + mixer["delta_scan"])
    per_row += 3 * full * (mixer["full_projections"] + mixer["attn_core"])
    per_row += 3 * a["num_hidden_layers"] * sum(moe.values())
    per_row += 3 * (d * d + d * feat_dim)
    return 2.0 * per_row * rows + 3 * 2 * rows * rows * feat_dim


def flops_per_image(model: str, size: int, global_batch: int, feat_dim: int = 128) -> float:
    return step_flops(model, size, global_batch, feat_dim) / global_batch


def expert_matmul_flops_per_step(model: str, size: int, rows: int) -> float:
    """Forward and backward of the grouped products (gate, up, down) over the
    held assignments of ``rows`` rows, all expert layers."""
    a = reference.arch(model)
    macs = expert_layer_macs_per_row(a, _tokens(a, size))["experts"]
    return 2.0 * macs * 3 * rows * a["num_hidden_layers"]


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
    side sets it (``flops_tokens.expert_matmul_min_seconds``'s signature)."""
    t_flops = expert_matmul_flops_per_step(model, size, rows) / peak_flops
    t_bytes = expert_matmul_min_bytes_per_step(model, size, rows) / peak_bytes_per_s
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")


def delta_scan_flops_per_step(model: str, size: int, rows: int) -> float:
    """Forward and backward of the chunked delta rule over ``rows`` rows, all
    Gated DeltaNet layers."""
    a = reference.arch(model)
    macs = mixer_macs_per_row(a, _tokens(a, size))["delta_scan"]
    return 2.0 * macs * 3 * rows * _layers_of_each(a)[0]


def delta_scan_min_bytes_per_step(model: str, size: int, rows: int) -> float:
    """The least traffic the delta rule needs, whatever computes it: the
    forward reads ``q``, ``k`` (key heads), ``v``, ``g``, ``beta`` (value heads)
    and writes ``o``; the backward reads those five and ``do`` and writes the
    gradients of the five. No state goes through memory."""
    a = reference.arch(model)
    tokens = _tokens(a, size)
    qk = tokens * a["linear_num_key_heads"] * a["linear_key_head_dim"]
    v = tokens * a["linear_num_value_heads"] * a["linear_value_head_dim"]
    gates = tokens * a["linear_num_value_heads"]
    inputs = 2 * qk + v + 2 * gates
    forward, backward = inputs + v, (inputs + v) + inputs
    return float(forward + backward) * BYTES * rows * _layers_of_each(a)[0]


def delta_scan_min_seconds(model, size, rows, peak_flops, peak_bytes_per_s):
    """The roofline of one step's delta rule on one chip, and which side
    sets it."""
    t_flops = delta_scan_flops_per_step(model, size, rows) / peak_flops
    t_bytes = delta_scan_min_bytes_per_step(model, size, rows) / peak_bytes_per_s
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
