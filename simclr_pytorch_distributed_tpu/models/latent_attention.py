"""Multi-head latent attention: keys and values out of one low-rank latent,
and one rotary key that all heads share.

The layer's equations, per row of ``T`` tokens, with ``a`` the pre-normed
input and ``H`` heads (DeepSeek-V3's layer without a query latent, as
Moonlight-16B-A3B publishes it):

- ``q = a W_q`` in ``H`` heads of ``nope_dim + rope_dim``: ``q_n``, ``q_r``;
- ``c = a W_kva`` of ``kv_rank + rope_dim``: the latent ``c_kv``, RMS-normed
  over ``kv_rank``, and ONE rotary key ``k_r`` of ``rope_dim``;
- ``kv = c_kv W_kvb`` in ``H`` heads of ``nope_dim + v_dim``: ``k_n``, ``v``;
- ``q_r`` and ``k_r`` turned by the rotary embedding at the token's raster
  index ``t`` (a text model's 1-D positions): slot ``m`` pairs dimensions
  ``(2m, 2m + 1)`` and turns them by ``t * theta ** (-2m / rope_dim)``. That
  is the pairing of ``deepseek_v3``'s published code, which then lays the
  turned pairs out as halves; the layout is the same permutation of ``q_r``
  and ``k_r`` and leaves every score as it is, so the pairs stay in place
  here;
- ``k = concat(k_n, k_r for every head)``; ``o[t, n] = sum_{s <= t}
  softmax_s(q[t, n] . k[s, n] / sqrt(nope_dim + rope_dim)) v[s, n]``, softmax
  in float32, then ``W_o``. No bias, and no weight absorption: training
  computes ``k_n`` and ``v`` from the latent. The rotary key's gradient is a
  sum over the heads.

The computation goes as ``models/sparse_attention.py``'s: a few rows at a
time under ``jax.checkpoint`` (``map_row_groups``) and, inside, a chunk of
``q_chunk`` queries at a time against the keys at or before its last query,
one row at a time, so that a ``[heads, q_chunk, keys]`` score block lives and
never ``[T, T]``. All of it is XLA's: ``ops/sparse_attention.py``'s kernel
pair takes one head width for q, k and v, which this layer does not have.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from simclr_pytorch_distributed_tpu.models.sparse_attention import (
    _chunks,
    map_row_groups,
    normal_init,
    rms_norm,
    tie_gradients,
)

# W_kva, the latent's norm, W_kvb, the rotary key's turn and its broadcast
SCOPE_LATENT = "latent"
# scores, causal mask, softmax, values
SCOPE_ATTN_CORE = "attn_core"


def rope_tables_1d(tokens: int, dim: int, theta: float):
    """``(cos, sin)``, each ``[tokens, dim / 2]`` float32: slot ``m`` at
    position ``t`` turns by ``t * theta ** (-m / (dim / 2))``."""
    inv_freq = theta ** (-jnp.arange(dim // 2, dtype=jnp.float32) / (dim // 2))
    angle = jnp.arange(tokens, dtype=jnp.float32)[:, None] * inv_freq
    return jnp.cos(angle), jnp.sin(angle)


def apply_rope_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``x`` is ``[..., T, heads, dim]``; pairs ``(2m, 2m + 1)`` turned in
    place."""
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    cos, sin = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def _attend_causal(q, k, v, first: int):
    """One row's queries ``first .. first + Q - 1`` against its keys ``0 ..
    S - 1``: ``q [Q, H, d]``, ``k [S, H, d]``, ``v [S, H, dv]`` -> ``[Q, H *
    dv]``."""
    Q, H, d = q.shape
    S = k.shape[0]
    with jax.named_scope(SCOPE_ATTN_CORE):
        causal = jnp.arange(S)[None, :] <= first + jnp.arange(Q)[:, None]
        logits = jnp.einsum("qhd,shd->hqs", q, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(causal, logits.astype(jnp.float32), -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs.astype(v.dtype), v).reshape(Q, -1)


def causal_attention(q, k, v, *, q_chunk: int):
    """All rows: ``q``/``k`` ``[R, T, H, d]``, ``v [R, T, H, dv]`` -> ``[R, T,
    H * dv]``, full multi-head, causal."""
    outs = []
    for first, last in _chunks(q.shape[1], q_chunk):
        one_row = jax.checkpoint(lambda row, first=first: _attend_causal(*row, first=first))
        outs.append(lax.map(one_row, (q[:, first:last], k[:, :last], v[:, :last])))
    return jnp.concatenate(outs, axis=1)


class LatentAttention(nn.Module):
    """The layer with its pre-norm and its residual, over tokens ``h [R, T,
    D]`` in raster order: returns ``h + W_o attention(rms(h))``."""

    n_heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    q_chunk: int
    rope_theta: float
    rms_eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h: jax.Array) -> jax.Array:
        R, T, D = h.shape
        H, r, dn, dr, dv = self.n_heads, self.kv_rank, self.nope_dim, self.rope_dim, self.v_dim

        w = {name: self.param(name, normal_init, shape) for name, shape in (
            ("q", (D, H * (dn + dr))), ("kv_a", (D, r + dr)), ("kv_b", (r, H * (dn + dv))),
            ("o", (H * dv, D)))}
        w.update({name: self.param(name, nn.initializers.ones, (n,))
                  for name, n in (("norm", D), ("kv_norm", r))})
        w, h = tie_gradients((w, h))
        w = {name: x.astype(self.dtype) for name, x in w.items()}
        cos, sin = rope_tables_1d(T, dr, self.rope_theta)

        def some_rows(h):
            n = h.shape[0]
            a = rms_norm(h, w["norm"], self.rms_eps).astype(self.dtype)
            q = (a @ w["q"]).reshape(n, T, H, dn + dr)
            q = jnp.concatenate([q[..., :dn], apply_rope_pairs(q[..., dn:], cos, sin)], axis=-1)
            with jax.named_scope(SCOPE_LATENT):
                c = a @ w["kv_a"]
                kv = (rms_norm(c[..., :r], w["kv_norm"], self.rms_eps) @ w["kv_b"]).reshape(
                    n, T, H, dn + dv)
                k_r = apply_rope_pairs(c[..., None, r:], cos, sin)  # one head
                k = jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(k_r, (n, T, H, dr))], axis=-1)
            o = causal_attention(q, k, kv[..., dn:], q_chunk=self.q_chunk)
            return h + (o @ w["o"]).astype(h.dtype)

        return map_row_groups(some_rows, h).reshape(h.shape)
