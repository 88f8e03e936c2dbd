"""Gated DeltaNet: linear attention whose state is corrected by the delta
rule and decayed by a learned gate (Qwen3-Next's linear-attention layer).

The layer's equations, per row of ``T`` tokens, with ``a`` the pre-normed
input, ``Hk`` key heads and ``Hv`` value heads of ``dk`` and ``dv``:

- ``[q, k, v, z] = a W_qkvz`` (``q``, ``k``: ``Hk x dk``; ``v``, ``z``:
  ``Hv x dv``) and ``[b, c] = a W_ba`` (``Hv`` each);
- ``[q, k, v] = silu(conv([q, k, v]))``: a causal depthwise convolution over
  the tokens, ``conv_width`` taps, no bias (``short_conv``);
- ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(c + dt_bias)``, one of
  each per value head and token: the log of the state's decay;
- ``q`` and ``k`` L2-normalised per head, ``q`` scaled by ``dk ** -0.5``;
  value head ``h`` reads key head ``h // (Hv / Hk)``;
- per value head a state ``S [dk, dv]``, zero at the first token:
  ``S_t = exp(g_t) S_{t-1}``, ``S_t += k_t (beta_t (v_t - S_t^T k_t))^T``,
  ``o_t = S_t^T q_t``;
- ``y = rms(o) * w * silu(z)`` per head, then ``W_out``.

The recurrence runs a chunk of ``chunk`` tokens at a time: the WY form of
the delta rule, all of a chunk's products at once and one small recurrence
over the chunks. Two paths compute it. ``chunked_delta_rule`` is XLA's,
forward and backward by autodiff, over ``q`` and ``k`` repeated to the value
heads; the tests hold it to the recurrence token by token
(``benchmark/reference_delta.delta_rule``). ``ops/delta_rule.py``'s Mosaic
kernel pair computes the same a row's chunks in one sweep, each head's state
in VMEM, over the key heads whole. The layer takes the second where its
owner says the program is one TPU's (``kernel``) and its own dtype and shape
allow it (``GatedDeltaNet.kernel_reason``). Likewise the convolution and its
``silu``: ``short_conv`` is XLA's path, ``ops/short_conv.py``'s Mosaic
kernel pair reads the projection's columns once a pass where ``kernel`` and
``GatedDeltaNet.conv_reason`` allow, on its own terms, so either pair may
engage without the other. The layer recomputes its own row groups
(``map_row_groups``), as the latent layer does.

Parameter layout: ``W_qkvz`` is ``[q | k | v | z]`` and ``W_ba`` ``[b | c]``,
each part head-major; the published checkpoint groups the columns by key
head, which is a permutation of the same columns. ``conv`` is ``[taps,
channels]`` with the last tap on the current token.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from simclr_pytorch_distributed_tpu.models.sparse_attention import (
    _interpret_kernel,
    map_row_groups,
    normal_init,
    rms_norm,
    tie_gradients,
)
from simclr_pytorch_distributed_tpu.ops import delta_rule as kernel_ops
from simclr_pytorch_distributed_tpu.ops import short_conv as conv_ops

# the whole layer: norm, projections, convolution, gates, scan, output
SCOPE_LINEAR = "linear_attn"
# the causal depthwise convolution and its activation
SCOPE_CONV = "short_conv"
# the chunked delta rule: the chunks' products and the recurrence over them
SCOPE_SCAN = "delta_scan"
L2_EPS = 1e-6


def a_log_init(key, shape, dtype=jnp.float32):
    """``log U(0, 16)``, Qwen3-Next's: per-token decays ``exp(-A softplus(.))``
    from nearly none to nearly all."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


def conv_init(key, shape, dtype=jnp.float32):
    """A depthwise convolution's default: uniform within ``1 / sqrt(taps)``."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def short_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x [R, T, C]`` through the causal depthwise convolution ``w [K, C]``:
    ``y[t] = sum_j w[j] x[t - K + 1 + j]``, zeros before the first token."""
    taps, tokens = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + tokens] * w[j] for j in range(taps))


def l2_normalise(x: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    return (x32 * lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + L2_EPS)).astype(x.dtype)


@jax.custom_vjp
def unit_lower_inverse(n: jax.Array) -> jax.Array:
    """``(I + n)^-1`` for ``n [..., C, C]`` strictly lower triangular, by
    diagonal blocks that double in size: ``[[A, 0], [X, D]]^-1 = [[A^-1, 0],
    [-D^-1 X A^-1, D^-1]]``, so that every product is of matrices no larger
    than the inverse itself. (The power series ``sum_k (-n)^k`` cancels terms
    as large as ``C choose C/2`` where a chunk's keys are alike and its decay
    slow, and returns garbage.) ``C`` is padded to a power of two with
    identity rows."""
    size = n.shape[-1]
    full = 1 << max(0, (size - 1).bit_length())
    batch = n.shape[:-2]
    if full != size:
        n = jnp.pad(n, [(0, 0)] * len(batch) + [(0, full - size)] * 2)
    inv = jnp.ones(batch + (full, 1, 1), n.dtype)  # the blocks of 1
    b = 1
    while b < full:
        m = full // (2 * b)
        pairs = jnp.diagonal(n.reshape(*batch, m, 2 * b, m, 2 * b), axis1=-4, axis2=-2)
        x = jnp.moveaxis(pairs, -1, -3)[..., b:, :b]  # [..., m, b, b]: below each pair
        a_inv, d_inv = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        low = -(d_inv @ x @ a_inv)
        inv = jnp.concatenate([jnp.concatenate([a_inv, jnp.zeros_like(low)], -1),
                               jnp.concatenate([low, d_inv], -1)], -2)
        b *= 2
    return inv.reshape(*batch, full, full)[..., :size, :size]


def _inverse_fwd(n):
    t = unit_lower_inverse(n)
    return t, t


def _inverse_bwd(t, dt):
    # d(M^-1) = -M^-1 dM M^-1; only the strictly lower entries of n are free
    tt = jnp.swapaxes(t, -1, -2)
    return (jnp.tril(-(tt @ dt @ tt), -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def chunked_delta_rule(q, k, v, g, beta, chunk: int):
    """The recurrence, ``chunk`` tokens at a time: ``q``, ``k`` ``[R, T, H,
    dk]``, ``v [R, T, H, dv]``, ``g``, ``beta`` ``[R, T, H]`` -> ``o [R, T, H,
    dv]`` (``T`` a multiple of ``chunk``).

    In a chunk with cumulative log decay ``G_i`` and state ``S`` at its
    start, the corrections ``u_i = beta_i (v_i - S_i^T k_i)`` solve ``(I + N)
    U = B V - B e^G K S`` with ``N[i, j] = beta_i e^(G_i - G_j) k_i . k_j``
    (``j < i``). With ``T = (I + N)^-1``, ``W = T B e^G K`` and ``U' = T B V``,
    which do not depend on ``S``: ``U = U' - W S``, the chunk's outputs
    ``e^G Q S + (Q K^T * e^(G_i - G_j), j <= i) U`` and the next state
    ``e^(G_C) S + (e^(G_C - G) K)^T U``. Every decay is the ``exp`` of a
    difference of ``G``, masked before the ``exp``: ``e^(-G_j)`` alone
    overflows once a chunk's decay passes about 88."""
    R, T, H, dk = q.shape
    n = T // chunk

    def chunks(x):  # [R, T, H, ...] -> [n, R, H, chunk, ...]
        x = x.reshape(R, n, chunk, H, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    dtype = v.dtype
    big = jnp.cumsum(g.astype(jnp.float32), axis=-1)  # G, [n, R, H, chunk]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(causal, -1)
    decay = jnp.exp(jnp.where(causal, big[..., :, None] - big[..., None, :], -jnp.inf))
    decay, beta = decay.astype(dtype), beta.astype(dtype)
    kk = jnp.einsum("...id,...jd->...ij", k, k)
    t = unit_lower_inverse(jnp.where(strict, kk * decay * beta[..., :, None], 0))
    w = t @ (k * (beta * jnp.exp(big).astype(dtype))[..., None])
    u_free = t @ (v * beta[..., None])
    p = jnp.where(causal, jnp.einsum("...id,...jd->...ij", q, k) * decay, 0)
    q_in = q * jnp.exp(big).astype(dtype)[..., None]
    last = big[..., -1]
    k_out = k * jnp.exp(last[..., None] - big).astype(dtype)[..., None]
    fade = jnp.exp(last).astype(dtype)[..., None, None]

    def one_chunk(s, x):
        w, u_free, p, q_in, k_out, fade = x
        u = u_free - w @ s
        o = q_in @ s + p @ u
        return fade * s + jnp.swapaxes(k_out, -1, -2) @ u, o

    s0 = jnp.zeros((R, H, dk, v.shape[-1]), dtype)
    _, o = lax.scan(one_chunk, s0, (w, u_free, p, q_in, k_out, fade))
    # [n, R, H, chunk, dv] -> [R, T, H, dv]
    return jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(R, T, H, -1)


class GatedDeltaNet(nn.Module):
    """The layer with its pre-norm and its residual, over tokens ``h [R, T,
    D]``: returns ``(h + W_out y, the mean of exp(g) over rows, tokens and
    value heads)``."""

    n_key_heads: int
    n_value_heads: int
    key_dim: int
    value_dim: int
    conv_width: int
    chunk: int
    rms_eps: float
    dtype: Any = jnp.float32
    # the chunked rule and the convolution through the kernel pairs of
    # ops/delta_rule.py and ops/short_conv.py. Set by the owner that knows
    # the mesh holds ONE device and the backend is a TPU
    # (train.supcon.build); the dtype and the row's shape can still say no,
    # to each on its own (kernel_reason, conv_reason).
    kernel: bool = False

    def chunk_of(self, tokens: int) -> int:
        """The scan's chunk for rows of ``tokens``: the whole row where
        ``chunk`` does not cut it."""
        return self.chunk if tokens % self.chunk == 0 else tokens

    def kernel_reason(self, tokens: int) -> Optional[str]:
        """Why rows of ``tokens`` keep XLA's path through this layer, or
        None: the kernel pair is float32 in and out, at a shape it tiles
        within its VMEM budget. ``__call__`` and ``plan_linear_attention``
        both ask here."""
        if self.dtype != jnp.float32:
            return f"compute dtype {jnp.dtype(self.dtype).name}"
        return kernel_ops.unsupported(tokens, self.chunk_of(tokens), self.n_key_heads,
                                      self.n_value_heads, self.key_dim, self.value_dim)

    def conv_reason(self, tokens: int) -> Optional[str]:
        """Why rows of ``tokens`` keep XLA's convolution (``short_conv``),
        or None: ops/short_conv.py's kernel pair is float32, over channels
        of whole lanes and rows of whole token blocks, within its VMEM
        budget. Independent of ``kernel_reason``: either kernel pair may
        engage without the other."""
        mixed = 2 * self.n_key_heads * self.key_dim + self.n_value_heads * self.value_dim
        return conv_ops.unsupported(tokens, mixed, self.conv_width, self.dtype)

    @nn.compact
    def __call__(self, h: jax.Array) -> tuple:
        R, T, D = h.shape
        Hk, Hv, dk, dv = self.n_key_heads, self.n_value_heads, self.key_dim, self.value_dim
        mixed = 2 * Hk * dk + Hv * dv  # q, k, v: the convolution's channels
        w = {name: self.param(name, normal_init, shape) for name, shape in (
            ("qkvz", (D, mixed + Hv * dv)), ("ba", (D, 2 * Hv)), ("o", (Hv * dv, D)))}
        w["conv"] = self.param("conv", conv_init, (self.conv_width, mixed))
        w["A_log"] = self.param("A_log", a_log_init, (Hv,))
        w.update({name: self.param(name, nn.initializers.ones, (n,))
                  for name, n in (("norm", D), ("dt_bias", Hv), ("out_norm", dv))})
        chunk = self.chunk_of(T)
        kernel = self.kernel and self.kernel_reason(T) is None
        conv_kernel = self.kernel and self.conv_reason(T) is None
        interpret = _interpret_kernel()
        with jax.named_scope(SCOPE_LINEAR):
            w, h = tie_gradients((w, h))
            w = {name: x if name in ("A_log", "dt_bias") else x.astype(self.dtype)
                 for name, x in w.items()}

            def some_rows(h):
                n = h.shape[0]
                a = rms_norm(h, w["norm"], self.rms_eps).astype(self.dtype)
                qkvz = a @ w["qkvz"]
                with jax.named_scope(SCOPE_CONV):
                    if conv_kernel:  # reads the first ``mixed`` columns where they lie
                        qkv = conv_ops.short_conv_silu(qkvz, w["conv"], interpret=interpret)
                    else:
                        qkv = jax.nn.silu(short_conv(qkvz[..., :mixed], w["conv"]))
                q = l2_normalise(qkv[..., :Hk * dk].reshape(n, T, Hk, dk)) / math.sqrt(dk)
                k = l2_normalise(qkv[..., Hk * dk:2 * Hk * dk].reshape(n, T, Hk, dk))
                if not kernel:
                    q, k = (jnp.repeat(x, Hv // Hk, axis=2) for x in (q, k))
                v = qkv[..., 2 * Hk * dk:]
                ba = (a @ w["ba"]).astype(jnp.float32)
                beta = jax.nn.sigmoid(ba[..., :Hv])
                g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[..., Hv:] + w["dt_bias"])
                with jax.named_scope(SCOPE_SCAN):
                    if kernel:
                        # the projections' layout as it comes: [rows, T, heads * d];
                        # the products' operands as XLA's default precision has them
                        o = kernel_ops.delta_rule(
                            q.reshape(n, T, Hk * dk), k.reshape(n, T, Hk * dk), v, g, beta,
                            n_key_heads=Hk, chunk=chunk,
                            operands=jnp.float32 if interpret else jnp.bfloat16,
                            interpret=interpret).reshape(n, T, Hv, dv)
                    else:
                        o = chunked_delta_rule(q, k, v.reshape(n, T, Hv, dv), g, beta, chunk)
                z = qkvz[..., mixed:].reshape(n, T, Hv, dv)
                y = (rms_norm(o, w["out_norm"], self.rms_eps).astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32))).astype(self.dtype)
                out = h + (y.reshape(n, T, Hv * dv) @ w["o"]).astype(h.dtype)
                return out, jnp.mean(jnp.exp(g))

            out, decay = map_row_groups(some_rows, h)
        return out.reshape(h.shape), lax.stop_gradient(jnp.mean(decay))
