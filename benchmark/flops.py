"""Operations and bytes that one training step needs, counted from the
configuration's shapes and from nothing the program reports.

A multiply-add is two operations. A convolution's backward pass costs its
forward twice over (gradient to the input, gradient to the weights); the
stem's input is the image, which needs no gradient, so the stem counts twice
and not three times. Nothing recomputed is counted, and the elementwise work
(augmentation, BN, ReLU, the optimizer) is left out: the count is a floor, so
a share of a peak that is computed from it cannot be flattered.
"""

from __future__ import annotations

import reference

BYTES = 4  # the configurations keep activations and weights in float32


def conv_macs(c: dict) -> int:
    """Multiply-adds of one convolution for one image, forward."""
    return c["hout"] ** 2 * c["k"] ** 2 * c["cin"] * c["cout"]


def forward_macs_per_view(model: str, size: int) -> int:
    return sum(conv_macs(c) for c in reference.conv_list(model, size))


def head_macs_per_view(model: str, feat_dim: int) -> int:
    f = reference.feature_dim(model)
    return f * f + f * feat_dim


def conv_flops_per_step(model: str, size: int, rows: int) -> float:
    """Forward and backward of every convolution over ``rows`` encoder rows."""
    total = 0
    for c in reference.conv_list(model, size):
        passes = 2 if c["name"] == "stem/conv" else 3
        total += 2 * conv_macs(c) * passes
    return float(total) * rows


def conv_min_bytes_per_step(model: str, size: int, rows: int) -> float:
    """The least traffic the convolutions need: each of the three passes
    reads its two operands and writes its result once."""
    total = 0
    for c in reference.conv_list(model, size):
        x = rows * c["hin"] ** 2 * c["cin"]
        y = rows * c["hout"] ** 2 * c["cout"]
        w = c["k"] ** 2 * c["cin"] * c["cout"]
        total += x + w + y  # forward
        total += x + y + w  # gradient to the weights
        if c["name"] != "stem/conv":
            total += y + w + x  # gradient to the input
    return float(total) * BYTES


def step_flops(model: str, size: int, global_batch: int, feat_dim: int = 128) -> float:
    """Everything counted for one step at ``global_batch`` images, two views
    each: convolutions, the dense head, and NT-Xent's similarity matrix
    (one product forward, two backward)."""
    rows = 2 * global_batch
    dense = 3 * 2 * head_macs_per_view(model, feat_dim) * rows
    loss = 3 * 2 * rows * rows * feat_dim
    return conv_flops_per_step(model, size, rows) + dense + loss


def flops_per_image(model: str, size: int, global_batch: int, feat_dim: int = 128) -> float:
    return step_flops(model, size, global_batch, feat_dim) / global_batch


def conv_min_seconds(model, size, rows, peak_flops, peak_bytes_per_s):
    """The roofline of one step's convolutions on one chip, and which side
    sets it."""
    t_flops = conv_flops_per_step(model, size, rows) / peak_flops
    t_bytes = conv_min_bytes_per_step(model, size, rows) / peak_bytes_per_s
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
