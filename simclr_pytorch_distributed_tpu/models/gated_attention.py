"""Gated full attention: grouped-query softmax attention whose output is
gated by a sigmoid read from the query projection (Qwen3-Next's full layer),
or without the gate (``output_gate`` False: LFM2's attention layer).

The layer's equations, per row of ``T`` tokens, with ``a`` the pre-normed
input, ``H`` query and ``G`` key-value heads of ``head_dim``:

- ``a W_q`` in ``H`` heads of ``2 head_dim``: each head's query, then its
  gate (heads of ``head_dim``, the queries alone, without the gate);
  ``k, v = a W_k, a W_v`` in ``G`` heads;
- ``q`` and ``k`` RMS-normed per head, then their first ``rope_dim``
  dimensions turned by the rotary embedding at the token's raster index
  (rotate-half pairs ``(m, m + rope_dim / 2)`` turned by ``t * theta ** (-m /
  (rope_dim / 2))``); the other dimensions pass;
- ``o[t, h] = sum_{s <= t} softmax_s(q[t, h] . k[s, h // (H/G)] /
  sqrt(head_dim)) v[s, h // (H/G)]``, softmax in float32
  (``latent_attention.causal_attention``);
- ``(o * sigmoid(gate)) W_o`` (``o W_o`` without the gate). No bias.

A few rows at a time under ``jax.checkpoint`` (``map_row_groups``), and all of
it XLA's: ``ops/sparse_attention.py``'s kernel pair takes heads of 128 and a
mask operand.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from simclr_pytorch_distributed_tpu.models.latent_attention import causal_attention, rope_tables_1d
from simclr_pytorch_distributed_tpu.models.sparse_attention import (
    apply_rope,
    map_row_groups,
    normal_init,
    rms_norm,
    tie_gradients,
)


def partial_rope(x, cos, sin):
    """``x [..., T, heads, d]`` with its first ``cos.shape[-1]`` dimensions
    turned (rotate-half) and the rest as they are."""
    turned = cos.shape[-1]
    if turned == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate([apply_rope(x[..., :turned], cos, sin), x[..., turned:]], axis=-1)


class GatedAttention(nn.Module):
    """The layer with its pre-norm and its residual, over tokens ``h [R, T,
    D]`` in raster order: returns ``h + W_o (attention(rms(h)) * gate)``,
    or ``h + W_o attention(rms(h))`` without ``output_gate``."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_dim: int
    q_chunk: int
    rope_theta: float
    rms_eps: float
    dtype: Any = jnp.float32
    # the sigmoid gate's columns in W_q and its product with the output
    output_gate: bool = True

    @nn.compact
    def __call__(self, h: jax.Array) -> jax.Array:
        R, T, D = h.shape
        H, G, d = self.n_heads, self.n_kv_heads, self.head_dim
        q_width = 2 * d if self.output_gate else d
        w = {name: self.param(name, normal_init, shape) for name, shape in (
            ("q", (D, H * q_width)), ("k", (D, G * d)), ("v", (D, G * d)), ("o", (H * d, D)))}
        w.update({name: self.param(name, nn.initializers.ones, (n,))
                  for name, n in (("norm", D), ("q_norm", d), ("k_norm", d))})
        w, h = tie_gradients((w, h))
        w = {name: x.astype(self.dtype) for name, x in w.items()}
        cos, sin = (jnp.concatenate([x, x], axis=-1)
                    for x in rope_tables_1d(T, self.rope_dim, self.rope_theta))

        def some_rows(h):
            n = h.shape[0]
            a = rms_norm(h, w["norm"], self.rms_eps).astype(self.dtype)
            q_gate = (a @ w["q"]).reshape(n, T, H, q_width)
            q = partial_rope(rms_norm(q_gate[..., :d], w["q_norm"], self.rms_eps), cos, sin)
            k = (a @ w["k"]).reshape(n, T, G, d)
            k = partial_rope(rms_norm(k, w["k_norm"], self.rms_eps), cos, sin)
            v = (a @ w["v"]).reshape(n, T, G, d)
            k, v = (jnp.repeat(x, H // G, axis=2) for x in (k, v))
            o = causal_attention(q, k, v, q_chunk=self.q_chunk)
            if self.output_gate:
                o = o * jax.nn.sigmoid(q_gate[..., d:].reshape(n, T, H * d))
            return h + (o @ w["o"]).astype(h.dtype)

        return map_row_groups(some_rows, h).reshape(h.shape)
