"""The gated short convolution: a token mixer with no attention and no
recurrence, a causal depthwise convolution between an input gate and an
output gate (LFM2's ``conv`` layer).

The layer's equations, per row of ``T`` tokens of width ``D``, with ``a``
the pre-normed input:

- ``[B, C, x] = a W_bcx`` (``D`` each, in that order of the columns);
- ``u = conv(B * x)``: causal and depthwise over the tokens, ``conv_width``
  taps, no bias and no activation (``gated_delta.short_conv``);
- ``y = (C * u) W_o``. No bias.

A few rows at a time under ``jax.checkpoint`` (``map_row_groups``), all of it
XLA's: ``ops/short_conv.py``'s kernel pair is four taps with a ``silu`` after
them.

Parameter layout: ``bcx`` is ``[D, 3 D]`` with ``B``'s columns first, then
``C``'s, then ``x``'s (the published ``in_proj``'s rows, split by
``chunk(3)``); ``conv`` is ``[taps, D]`` with the last tap on the current
token.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from simclr_pytorch_distributed_tpu.models.gated_delta import conv_init, short_conv
from simclr_pytorch_distributed_tpu.models.sparse_attention import (
    map_row_groups,
    normal_init,
    rms_norm,
    tie_gradients,
)

# the whole layer: norm, projections, gates, convolution
SCOPE_MIXER = "conv_mixer"
# B * x, the convolution, C * u
SCOPE_GATED = "gated_conv"


def gated_conv(bcx: jax.Array, w: jax.Array) -> jax.Array:
    """``C * conv(B * x)`` for the projection ``bcx [R, T, 3 D]`` and taps
    ``w [K, D]``."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return c * short_conv(b * x, w)


class GatedShortConv(nn.Module):
    """The layer with its pre-norm and its residual, over tokens ``h [R, T,
    D]`` in raster order: returns ``h + W_o (C * conv(B * x))``."""

    conv_width: int
    rms_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h: jax.Array) -> jax.Array:
        D = h.shape[-1]
        w = {name: self.param(name, normal_init, shape)
             for name, shape in (("bcx", (D, 3 * D)), ("o", (D, D)))}
        w["conv"] = self.param("conv", conv_init, (self.conv_width, D))
        w["norm"] = self.param("norm", nn.initializers.ones, (D,))
        with jax.named_scope(SCOPE_MIXER):
            w, h = tie_gradients((w, h))
            w = {name: x.astype(self.dtype) for name, x in w.items()}

            def some_rows(h):
                a = rms_norm(h, w["norm"], self.rms_eps).astype(self.dtype)
                bcx = a @ w["bcx"]
                with jax.named_scope(SCOPE_GATED):
                    y = gated_conv(bcx, w["conv"])
                return h + (y @ w["o"]).astype(h.dtype)

            return map_row_groups(some_rows, h).reshape(h.shape)
