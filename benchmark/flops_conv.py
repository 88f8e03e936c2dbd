"""Operations and bytes that one training step over patch tokens needs
through LFM2's block, counted from the configuration's shapes and from
nothing the program reports (``flops_delta.py``'s part for
``reference_conv``'s model).

A multiply-add is two operations; a product's backward pass costs its forward
twice over (gradient to each operand). The patch embedding counts twice: its
input is the image, which needs no gradient. Per row of ``T`` tokens a layer
has its mixer and its feed-forward layer. A gated short-convolution mixer:
``W_bcx`` and ``W_o``, and the gated convolution's core
(``gated_conv_macs_per_row``: ``B x``, the taps, ``C u``). An attention
mixer: ``W_q``, ``W_k``, ``W_v``, ``W_o`` and scores and values over the
``T (T + 1) / 2`` causal pairs, ``head_dim`` multiply-adds each in every
head. The dense layer: its three products. The expert layer: the router and
the routed products over the assignments that land on the held experts when
the load is balanced (``per_token * held / n_experts`` a token). Nothing
recomputed is counted, and the elementwise work (the norms, the rotary
embedding, the softmaxes, the top-k), the augmentation and the optimizer
are left out: the count is a floor. The core of the gated convolution is
in the step's count although it is elementwise: it is the mechanism this
block brings, and its floor (``gated_conv_min_seconds``) is its bytes.
"""

from __future__ import annotations

import reference_conv as reference

BYTES = 4  # float32 activations and weights


def _tokens(a: dict, size: int) -> int:
    return (size // a["patch_size"]) ** 2


def _conv_layers(a: dict) -> int:
    return a["layer_types"].count("conv")


def _expert_layers(a: dict) -> int:
    return a["num_hidden_layers"] - a["num_dense_layers"]


def gated_conv_macs_per_row(a: dict, tokens: int) -> int:
    """Multiply-adds of one gated convolution's core for one row, forward:
    ``B x``, ``conv_L_cache`` taps and ``C u`` in every channel."""
    return tokens * a["hidden_size"] * (a["conv_L_cache"] + 2)


def mixer_macs_per_row(a: dict, tokens: int) -> dict:
    """Multiply-adds of one layer's mixer for one row, forward, of each kind."""
    d = a["hidden_size"]
    heads, kv, hd = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    return {
        "conv_projections": tokens * d * 4 * d,
        "gated_conv": gated_conv_macs_per_row(a, tokens),
        "attn_projections": tokens * d * (2 * heads * hd + 2 * kv * hd),
        "attn_core": tokens * (tokens + 1) // 2 * heads * 2 * hd,
    }


def held_assignments_per_token(a: dict) -> float:
    return a["num_experts_per_tok"] * a["experts_held"][1] / a["num_experts"]


def feed_forward_macs_per_row(a: dict, tokens: int) -> dict:
    """Multiply-adds of one dense layer and of one expert layer for one row,
    forward."""
    d = a["hidden_size"]
    return {
        "dense": tokens * 3 * d * a["intermediate_size"],
        "router": tokens * d * a["num_experts"],
        "experts": tokens * held_assignments_per_token(a) * 3 * d * a["moe_intermediate_size"],
    }


def step_flops(model: str, size: int, global_batch: int, feat_dim: int = 128) -> float:
    """Everything counted for one step at ``global_batch`` images, two views
    each: patch embedding, the layers, the dense head, NT-Xent's similarity
    matrix (one product forward, two backward)."""
    a = reference.arch(model)
    rows, tokens, d = 2 * global_batch, _tokens(a, size), a["hidden_size"]
    conv = _conv_layers(a)
    mixer, ff = mixer_macs_per_row(a, tokens), feed_forward_macs_per_row(a, tokens)
    per_row = 2 * tokens * a["patch_size"] ** 2 * 3 * d  # embedding: forward, weights
    per_row += 3 * conv * (mixer["conv_projections"] + mixer["gated_conv"])
    per_row += 3 * (a["num_hidden_layers"] - conv) * (mixer["attn_projections"]
                                                      + mixer["attn_core"])
    per_row += 3 * a["num_dense_layers"] * ff["dense"]
    per_row += 3 * _expert_layers(a) * (ff["router"] + ff["experts"])
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


def gated_conv_flops_per_step(model: str, size: int, rows: int) -> float:
    """Forward and backward of the gated convolutions' core over ``rows``
    rows, all conv layers."""
    a = reference.arch(model)
    return 2.0 * gated_conv_macs_per_row(a, _tokens(a, size)) * 3 * rows * _conv_layers(a)


def gated_conv_min_bytes_per_step(model: str, size: int, rows: int) -> float:
    """The least traffic the core needs, whatever computes it: the forward
    reads ``B``, ``C``, ``x`` and the taps and writes ``y``; the backward reads
    the three, the taps and ``dy`` and writes the gradients of the three and
    of the taps (the taps once a layer, the rest once a row). Nothing in
    between goes through memory."""
    a = reference.arch(model)
    channels = _tokens(a, size) * a["hidden_size"]
    taps = a["conv_L_cache"] * a["hidden_size"]
    forward, backward = 4 * channels * rows + taps, 7 * channels * rows + 2 * taps
    return float(forward + backward) * BYTES * _conv_layers(a)


def gated_conv_min_seconds(model, size, rows, peak_flops, peak_bytes_per_s):
    """The roofline of one step's gated convolutions on one chip, and which
    side sets it."""
    t_flops = gated_conv_flops_per_step(model, size, rows) / peak_flops
    t_bytes = gated_conv_min_bytes_per_step(model, size, rows) / peak_bytes_per_s
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
