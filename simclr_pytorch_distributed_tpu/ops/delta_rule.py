"""The chunked gated delta rule in one Mosaic kernel pair.

What ``models/gated_delta.chunked_delta_rule`` computes, in its WY form and
with its masked-difference decays: per value head a ``[dk, dv]`` float32
state, zero at a row's first token, and per chunk of ``C`` tokens with
cumulative log decay ``G`` and the state ``S`` at its start

- ``D = exp(G_i - G_j)`` on and below the diagonal, 0 above;
- ``N = (k k^T * D * beta_i)`` strictly below the diagonal,
  ``T = (I + N)^-1`` by diagonal blocks that double in size;
- ``W = T (k * beta e^G)``, ``U' = T (v * beta)``, ``u = U' - W S``;
- ``o = (q * e^G) S + (q k^T * D, causal) u``;
- the next state ``e^(G_C) S + (k * e^(G_C - G))^T u``.

In XLA every chunk's ``[C, C]`` blocks, ``W`` and ``U'`` go through HBM and
the recurrence is a scan of small dependent products; here a grid step is
one chunk of a few key heads and their value heads, a row's chunks run in
order on the innermost ("arbitrary") grid axis, each value head's state
lives in VMEM scratch, and only ``o`` (and, for the backward pass, each
chunk's starting state) leaves the kernel.

Value head ``h`` reads key head ``h // (Hv / Hk)``. A key head's value heads
go through the chunk-local work together, their chunks one below the other:
``M = (Hv / Hk) C`` rows (128 at Qwen3-Next's 2 x 64), so that ``k k^T``,
``q k^T``, the decays, ``N``, the inverse's doublings, ``W``, ``U'`` and ``P
u`` are each one ``[M, M]`` or ``[M, d]`` block with the heads' ``[C, C]``
blocks on its diagonal and zeros (masked, or zero terms of a product) off
it; only the products against a head's own state are made a head at a time.

Layouts are the projections' own: ``q``, ``k`` ``[R, T, Hk*dk]``, ``v`` and
``o`` ``[R, T, Hv*dv]``, a chunk of one head a ``[C, d]`` block. ``g`` and
``beta`` (``[R, T, Hv]``, 2 MB a row group) are regrouped to ``[R, blocks,
n, M, key heads a step]``, a key head's stacked chunk a column.

Backward, one kernel: the chunks in reverse, each value head's ``dS``
carried in VMEM scratch, every chunk-local quantity recomputed from the
inputs and the chunk's starting state, which the forward wrote out
(``[R, n, Hv, dk, dv]`` float32). Gives ``dq``, ``dk`` (summed over a key
head's value heads), ``dv``, ``dg`` and ``dbeta``; the inverse's gradient is
``-(T^T dT T^T)`` strictly below the diagonal, as
``gated_delta._inverse_bwd`` takes it.

Precision: every product reads its operands in ``operands`` (bfloat16 on the
TPU, where XLA's default precision rounds the same operands; float32 in the
interpreter's tests, where XLA's CPU products are exact) and accumulates in
float32; the cumulative sum, the ``exp``s, the decays, the state and its
update are float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from simclr_pytorch_distributed_tpu.ops.sparse_attention import _VMEM_BUDGET

NAME = "delta_rule"
KEY_HEADS = 4  # key heads a grid step at most, each with its value heads
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def key_heads_per_step(n_key_heads: int) -> int:
    """The most key heads, up to ``KEY_HEADS``, that cut ``n_key_heads``."""
    return next(h for h in range(KEY_HEADS, 0, -1) if n_key_heads % h == 0)


def vmem_bytes(chunk: int, n_key_heads: int, n_value_heads: int, key_dim: int,
               value_dim: int) -> int:
    """VMEM of a backward grid step, which needs more than a forward one at
    every geometry the chip's compiler was asked about: ``q``, ``k``, ``v``,
    ``do``, ``g``, ``beta``, the chunk's starting states and the five
    gradients, double-buffered (``g`` and ``beta`` blocks padded to 128
    lanes), the ``dS`` scratch, and the temporaries that the compiler counts
    beside them: 8 ``[M, d]`` float32 arrays a key head of the step, 6 ``[M,
    M]``, a quarter of the states and 0.5 MiB (fitted from above to
    Mosaic's own counts at chunks of 32-256, heads of 128-384 and 1-8 value
    heads a key head: tests/test_tpu_aot_compile.py asks it at the edge)."""
    kh = key_heads_per_step(n_key_heads)
    per_key = n_value_heads // n_key_heads
    m, d = per_key * chunk, max(key_dim, value_dim)
    qk = chunk * kh * key_dim * 4
    vo = chunk * kh * per_key * value_dim * 4
    gate = m * 128 * 4
    state = kh * per_key * key_dim * value_dim * 4
    blocks = 2 * (4 * qk + 3 * vo + 4 * gate + state) + state
    return blocks + 4 * m * (8 * kh * d + 6 * m) + state // 4 + (1 << 19)


def unsupported(tokens: int, chunk: int, n_key_heads: int, n_value_heads: int,
                key_dim: int, value_dim: int) -> Optional[str]:
    """Why the kernels do not take this geometry, or None if they do."""
    if key_dim % 128 or value_dim % 128:
        return f"head widths {key_dim} / {value_dim} are not multiples of 128 lanes"
    if n_value_heads % n_key_heads:
        return f"{n_value_heads} value heads do not group over {n_key_heads} key heads"
    if tokens % chunk or chunk % 8:
        return f"chunks of {chunk} do not cut {tokens} tokens into multiples of 8"
    need = vmem_bytes(chunk, n_key_heads, n_value_heads, key_dim, value_dim)
    if need > _VMEM_BUDGET:
        return (f"chunks of {chunk} x heads of {key_dim} / {value_dim} need "
                f"{need / 2**20:.1f} MiB of VMEM a step (budget {_VMEM_BUDGET >> 20})")
    return None


def _dot(a, b, dims, operands):
    return lax.dot_general(a.astype(operands), b.astype(operands), dims,
                           preferred_element_type=jnp.float32)


class _Grid:
    """Index masks of a key head's ``[M, M]`` block, its ``per_key`` value
    heads' chunks of ``chunk`` tokens one below the other."""

    def __init__(self, per_key: int, chunk: int):
        m = per_key * chunk
        rows = lax.broadcasted_iota(jnp.int32, (m, m), 0)
        cols = lax.broadcasted_iota(jnp.int32, (m, m), 1)
        ids = lax.broadcasted_iota(jnp.int32, (m, 1), 0)

        def within(x, h):
            return (x >= h * chunk) & (x < (h + 1) * chunk)

        # each head's rows, and its chunk's [C, C] block (from ranges: no
        # comparison of booleans, which Mosaic does not lower)
        self.heads = [within(ids, h) for h in range(per_key)]
        same = functools.reduce(jnp.logical_or, [within(rows, h) & within(cols, h)
                                                 for h in range(per_key)])
        base = sum(jnp.where(rows >= h * chunk, chunk, 0) for h in range(1, per_key))
        li, lj = rows - base, cols - base  # within the chunk, where ``same``
        self.chunk = chunk
        self.same, self.eye = same, rows == cols
        self.causal, self.strict = same & (lj <= li), same & (lj < li)
        self.before = same & (lj >= li)  # the cumulative sum's transpose
        self.last = same & (lj == chunk - 1)
        self.last_row = functools.reduce(jnp.logical_or, [ids == (h + 1) * chunk - 1
                                                          for h in range(per_key)])
        # the inverse's doublings: pairs of blocks of 1, 2, 4, ... in a chunk,
        # and the lower block of each pair
        self.pairs, shift = [], 0
        while (1 << shift) < chunk:
            pair = same & ((li >> (shift + 1)) == (lj >> (shift + 1)))
            self.pairs.append(pair & ((li >> shift) != (lj >> shift)))
            shift += 1

    def row(self, col):
        """``[M, 1] -> [1, M]``, exactly (one term a sum)."""
        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=0, keepdims=True)

    def col(self, row):
        """``[1, M] -> [M, 1]``, exactly."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def rows_of(self, x, m):
        """Head ``m``'s ``[C, ...]`` rows of a stacked ``[M, ...]``."""
        return x[m * self.chunk:(m + 1) * self.chunk]


class _Decays:
    """A key head's stacked chunk-local decays from its log decays ``g [M,
    1]``: ``big`` the cumulative ``G`` within each chunk, ``decay`` the masked
    ``exp`` of its differences, ``eg = e^G``, ``out = e^(G_C - G)`` and
    ``fade = e^(G_C)``, the last two ``[M, 1]`` with each chunk's ``G_C``."""

    def __init__(self, grid: _Grid, g):
        self.big = jnp.sum(jnp.where(grid.causal, grid.row(g), 0.0), axis=1, keepdims=True)
        big_row = grid.row(self.big)
        diff = jnp.where(grid.causal, self.big - big_row, 0.0)
        self.decay = jnp.where(grid.causal, jnp.exp(diff), 0.0)
        self.eg = jnp.exp(self.big)
        last = jnp.sum(jnp.where(grid.last, big_row, 0.0), axis=1, keepdims=True)
        self.out = jnp.exp(last - self.big)
        self.fade = jnp.exp(last)


def _unit_lower_inverse(grid: _Grid, n, operands):
    """``(I + n)^-1`` for ``n [M, M]`` strictly lower triangular within each
    chunk, as ``gated_delta.unit_lower_inverse``: ``[[A, 0], [X, D]]^-1 =
    [[A^-1, 0], [-D^-1 X A^-1, D^-1]]`` on diagonal blocks of 1, 2, 4, ... at
    once, the block-diagonal inverse so far and each pair's ``X`` held as
    ``[M, M]`` matrices with zeros elsewhere (the products' other terms are
    zeros). Blocks of 1 are their own inverse, so the first doubling is
    ``I - X`` with no product."""
    pairs = grid.pairs
    t = jnp.where(grid.eye, 1.0, 0.0) - jnp.where(pairs[0], n, 0.0)
    for pair in pairs[1:]:
        t = t - _dot(_dot(t, jnp.where(pair, n, 0.0), _NN, operands), t, _NN, operands)
    return t


def _stack(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _stacked(ref, heads, width: int):
    """The ``[C, width]`` blocks of ``heads`` in a ``[C, heads * width]``
    ref, one below the other (a key head's once for each of its value
    heads)."""
    return _stack([ref[:, h * width:(h + 1) * width] for h in heads])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, chunk: int, key_dim: int,
                value_dim: int, per_key: int, operands, keep_states: bool):
    states_ref, s_ref = rest if keep_states else (None, rest[0])
    dk, dv = key_dim, value_dim
    grid = _Grid(per_key, chunk)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def dot(a, b, dims=_NN):
        return _dot(a, b, dims, operands)

    for j in range(k_ref.shape[1] // dk):
        heads = range(j * per_key, (j + 1) * per_key)
        q, k = _stacked(q_ref, [j] * per_key, dk), _stacked(k_ref, [j] * per_key, dk)
        v = _stacked(v_ref, heads, dv)
        d = _Decays(grid, g_ref[:, j:j + 1])
        beta = beta_ref[:, j:j + 1]
        t = _unit_lower_inverse(grid, jnp.where(grid.strict, dot(k, k, _NT) * d.decay * beta, 0.0),
                                operands)
        w = dot(t, k * (beta * d.eg))
        u_free = dot(t, v * beta)
        p = jnp.where(grid.causal, dot(q, k, _NT) * d.decay, 0.0)
        q_in, k_out = q * d.eg, k * d.out
        states, us = [], []
        for m, h in enumerate(heads):
            s = s_ref[h]
            if keep_states:
                states_ref[h] = s
            states.append(s)
            us.append(grid.rows_of(u_free, m) - dot(grid.rows_of(w, m), s))
        pu = dot(p, _stack(us))
        for m, h in enumerate(heads):
            s = states[m]
            o_ref[:, h * dv:(h + 1) * dv] = dot(grid.rows_of(q_in, m), s) + grid.rows_of(pu, m)
            fade = grid.rows_of(d.fade, m)[:1]
            s_ref[h] = fade * s + dot(grid.rows_of(k_out, m), us[m], _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_ref, *, chunk: int, key_dim: int,
                value_dim: int, per_key: int, operands):
    dk, dv = key_dim, value_dim
    grid = _Grid(per_key, chunk)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def dot(a, b, dims=_NN):
        return _dot(a, b, dims, operands)

    def per_head_sum(x):  # [M, d] -> [C, d]: the key head's share of each value head's
        return sum(grid.rows_of(x, m) for m in range(per_key))

    for j in range(k_ref.shape[1] // dk):
        heads = range(j * per_key, (j + 1) * per_key)
        q, k = _stacked(q_ref, [j] * per_key, dk), _stacked(k_ref, [j] * per_key, dk)
        v, do = _stacked(v_ref, heads, dv), _stacked(do_ref, heads, dv)
        d = _Decays(grid, g_ref[:, j:j + 1])
        beta = beta_ref[:, j:j + 1]
        # the chunk's forward, as the forward kernel computes it
        kk, qk = dot(k, k, _NT), dot(q, k, _NT)
        kk_decay = kk * d.decay
        t = _unit_lower_inverse(grid, jnp.where(grid.strict, kk_decay * beta, 0.0), operands)
        kb, vb = k * (beta * d.eg), v * beta
        w = dot(t, kb)
        u_free = dot(t, vb)
        p = jnp.where(grid.causal, qk * d.decay, 0.0)
        q_in, k_out = q * d.eg, k * d.out
        # head by head: the next state fade S + k_out^T u, o's q_in S, u = U' - W S
        us, d_k_outs, d_us, d_q_ins, d_fades = [], [], [], [], []
        for m, h in enumerate(heads):
            s, ds_next = states_ref[h], ds_ref[h]
            u = grid.rows_of(u_free, m) - dot(grid.rows_of(w, m), s)
            us.append(u)
            d_fades.append(jnp.sum(jnp.sum(s * ds_next, axis=1, keepdims=True), axis=0,
                                   keepdims=True))
            d_k_outs.append(dot(u, ds_next, _NT))
            d_us.append(dot(grid.rows_of(k_out, m), ds_next))
            d_q_ins.append(dot(grid.rows_of(do, m), s, _NT))
        u = _stack(us)
        du = _stack(d_us) + dot(p, do, _TN)  # and o's P u
        d_p = jnp.where(grid.causal, dot(do, u, _NT), 0.0)
        d_ws = []
        for m, h in enumerate(heads):
            s, ds_next, du_m = states_ref[h], ds_ref[h], grid.rows_of(du, m)
            d_ws.append(-dot(du_m, s, _NT))
            fade = grid.rows_of(d.fade, m)[:1]
            ds_ref[h] = (fade * ds_next + dot(grid.rows_of(q_in, m), grid.rows_of(do, m), _TN)
                         - dot(grid.rows_of(w, m), du_m, _TN))
        d_w, d_k_out, d_q_in = _stack(d_ws), _stack(d_k_outs), _stack(d_q_ins)
        # W = T kb, U' = T vb, T = (I + N)^-1
        d_t = dot(du, vb, _NT) + dot(d_w, kb, _NT)
        d_vb, d_kb = dot(t, du, _TN), dot(t, d_w, _TN)
        d_n = jnp.where(grid.strict, -dot(dot(t, d_t, _TN), t, _NT), 0.0)
        # N = kk * decay * beta below the diagonal, P = qk * decay on and below
        d_kk_decay = d_n * beta
        d_beta = jnp.sum(d_n * kk_decay, axis=1, keepdims=True)
        d_kk = d_kk_decay * d.decay
        d_qk = d_p * d.decay
        d_diff = jnp.where(grid.causal, (d_kk_decay * kk + d_p * qk) * d.decay, 0.0)
        # q_in = q e^G, kb = k beta e^G, vb = v beta, k_out = k e^(G_C - G)
        d_eg = jnp.sum(d_q_in * q, axis=1, keepdims=True)
        d_beta_eg = jnp.sum(d_kb * k, axis=1, keepdims=True)
        d_beta = d_beta + d_beta_eg * d.eg + jnp.sum(d_vb * v, axis=1, keepdims=True)
        d_eg = d_eg + d_beta_eg * beta
        d_v = d_vb * beta
        d_out = jnp.sum(d_k_out * k, axis=1, keepdims=True) * d.out
        d_fade = sum(jnp.where(grid.heads[m], f, 0.0) for m, f in enumerate(d_fades))
        d_last = (jnp.sum(jnp.where(grid.same, grid.row(d_out), 0.0), axis=1, keepdims=True)
                  + d_fade * d.fade)
        # G: through e^G, e^(G_C - G), G_C and the differences; g by the
        # cumulative sum's transpose
        d_big = (d_eg * d.eg - d_out + jnp.sum(d_diff, axis=1, keepdims=True)
                 - grid.col(jnp.sum(d_diff, axis=0, keepdims=True))
                 + jnp.where(grid.last_row, d_last, 0.0))
        dg_ref[:, j:j + 1] = jnp.sum(jnp.where(grid.before, grid.row(d_big), 0.0), axis=1,
                                     keepdims=True)
        dbeta_ref[:, j:j + 1] = d_beta
        for m, h in enumerate(heads):
            dv_ref[:, h * dv:(h + 1) * dv] = grid.rows_of(d_v, m)
        d_q = d_q_in * d.eg + dot(d_qk, k)
        d_k = (d_kb * (beta * d.eg) + d_k_out * d.out + dot(d_qk, q, _TN) + dot(d_kk, k)
               + dot(d_kk, k, _TN))
        dq_ref[:, j * dk:(j + 1) * dk] = per_head_sum(d_q)
        dk_ref[:, j * dk:(j + 1) * dk] = per_head_sum(d_k)


def _geometry(q, v, g, n_key_heads: int, chunk: int):
    R, T, _ = q.shape
    n_value_heads = g.shape[-1]
    kh = key_heads_per_step(n_key_heads)
    per_key = n_value_heads // n_key_heads
    return dict(R=R, T=T, n=T // chunk, kh=kh, vh=kh * per_key, per_key=per_key,
                blocks=n_key_heads // kh, dk=q.shape[-1] // n_key_heads,
                dv=v.shape[-1] // n_value_heads)


def _specs(geo: dict, chunk: int, reverse: bool):
    """Block specs over the grid ``(row, head block, chunk)``, chunks in
    order or, ``reverse``, last first: a chunk of the block's key heads, of
    its value heads, its key heads' stacked gates, and its value heads'
    states."""
    n, kh = geo["n"], geo["kh"]
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    keys = pl.BlockSpec((None, chunk, kh * geo["dk"]), lambda r, b, c: (r, at(c), b))
    values = pl.BlockSpec((None, chunk, geo["vh"] * geo["dv"]), lambda r, b, c: (r, at(c), b))
    gates = pl.BlockSpec((None, None, None, geo["per_key"] * chunk, kh),
                         lambda r, b, c: (r, b, at(c), 0, 0))
    states = pl.BlockSpec((None, None, geo["vh"], geo["dk"], geo["dv"]),
                          lambda r, b, c: (r, at(c), b, 0, 0))
    return keys, values, gates, states


def _by_blocks(x, geo: dict, chunk: int):
    """``[R, T, Hv] -> [R, blocks, n, per_key * chunk, key heads a step]``:
    a key head's value heads' chunks one below the other, a column."""
    R, n, kh, per_key = geo["R"], geo["n"], geo["kh"], geo["per_key"]
    x = x.reshape(R, n, chunk, geo["blocks"], kh, per_key)
    return x.transpose(0, 3, 1, 5, 2, 4).reshape(R, geo["blocks"], n, per_key * chunk, kh)


def _by_heads(x, geo: dict, chunk: int):
    """``_by_blocks``' inverse."""
    R, n, kh, per_key = geo["R"], geo["n"], geo["kh"], geo["per_key"]
    x = x.reshape(R, geo["blocks"], n, per_key, chunk, kh).transpose(0, 2, 4, 1, 5, 3)
    return x.reshape(R, n * chunk, -1)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _chunk_macs(geo: dict, chunk: int) -> int:
    """Multiply-adds of the forward's products for one key head's chunk as
    the kernel makes them: ``k k^T``, ``q k^T``, the inverse's doublings,
    ``W``, ``U'`` and ``P u`` over the stacked ``M`` rows, and each value
    head's ``W S``, ``e^G Q S`` and state update."""
    m, dk, dv, per_key = geo["per_key"] * chunk, geo["dk"], geo["dv"], geo["per_key"]
    doublings = max(0, (chunk - 1).bit_length() - 1)
    return (2 * m * m * dk + 2 * doublings * m ** 3 + m * m * (dk + 2 * dv)
            + 3 * per_key * chunk * dk * dv)


def _forward_call(q, k, v, g, beta, *, n_key_heads: int, chunk: int, operands,
                  keep_states: bool, interpret: bool):
    """``o [R, T, Hv*dv]`` float32 and, ``keep_states``, each chunk's starting
    states ``[R, n, Hv, dk, dv]`` float32 (else None)."""
    geo = _geometry(q, v, g, n_key_heads, chunk)
    R, n, dk, dv = geo["R"], geo["n"], geo["dk"], geo["dv"]
    keys, values, gates, states = _specs(geo, chunk, reverse=False)
    state_shape = jax.ShapeDtypeStruct((R, n, g.shape[-1], dk, dv), jnp.float32)
    chunks = R * n * n_key_heads  # chunks of key heads
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, key_dim=dk, value_dim=dv,
                          per_key=geo["per_key"], operands=operands, keep_states=keep_states),
        grid=(R, geo["blocks"], n),
        in_specs=[keys, keys, values, gates, gates],
        out_specs=[values] + [states] * keep_states,
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32)] + [state_shape] * keep_states,
        scratch_shapes=[pltpu.VMEM((geo["vh"], dk, dv), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * chunks * _chunk_macs(geo, chunk),
            transcendentals=R * n * g.shape[-1] * chunk * (chunk + 3),
            bytes_accessed=4 * (q.size + k.size + 2 * v.size + 2 * g.size
                                + keep_states * state_shape.size)),
        interpret=interpret,
        name=NAME + "_fwd",
    )(q, k, v, _by_blocks(g, geo, chunk), _by_blocks(beta, geo, chunk))
    return out[0], (out[1] if keep_states else None)


def _backward_call(q, k, v, g, beta, states, do, *, n_key_heads: int, chunk: int, operands,
                   interpret: bool):
    """``(dq, dk, dv, dg, dbeta)`` float32, in the inputs' layouts."""
    geo = _geometry(q, v, g, n_key_heads, chunk)
    R, n = geo["R"], geo["n"]
    keys, values, gates, state = _specs(geo, chunk, reverse=True)
    blocked = _by_blocks(g, geo, chunk)
    chunks = R * n * n_key_heads
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, key_dim=geo["dk"], value_dim=geo["dv"],
                          per_key=geo["per_key"], operands=operands),
        grid=(R, geo["blocks"], n),
        in_specs=[keys, keys, values, gates, gates, state, values],
        out_specs=[keys, keys, values, gates, gates],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32) for x in (q, k, v, blocked, blocked)],
        scratch_shapes=[pltpu.VMEM((geo["vh"], geo["dk"], geo["dv"]), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=6 * chunks * _chunk_macs(geo, chunk),
            transcendentals=R * n * g.shape[-1] * chunk * (chunk + 3),
            bytes_accessed=4 * (2 * q.size + 2 * k.size + 3 * v.size + 4 * g.size
                                + states.size)),
        interpret=interpret,
        name=NAME + "_bwd",
    )(q, k, v, blocked, _by_blocks(beta, geo, chunk), states, do)
    return dq, dk, dv, _by_heads(dg, geo, chunk), _by_heads(dbeta, geo, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rule(q, k, v, g, beta, n_key_heads, chunk, operands, interpret):
    return _forward_call(q, k, v, g, beta, n_key_heads=n_key_heads, chunk=chunk,
                         operands=operands, keep_states=False, interpret=interpret)[0]


def _rule_fwd(q, k, v, g, beta, n_key_heads, chunk, operands, interpret):
    o, states = _forward_call(q, k, v, g, beta, n_key_heads=n_key_heads, chunk=chunk,
                              operands=operands, keep_states=True, interpret=interpret)
    return o, (q, k, v, g, beta, states)


def _rule_bwd(n_key_heads, chunk, operands, interpret, res, do):
    return _backward_call(*res, do, n_key_heads=n_key_heads, chunk=chunk,
                          operands=operands, interpret=interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def delta_rule(q, k, v, g, beta, *, n_key_heads: int, chunk: int, operands=jnp.bfloat16,
               interpret: bool = False):
    """``o [R, T, Hv*dv]`` float32 of ``q``, ``k`` ``[R, T, Hk*dk]`` (``q``
    scaled), ``v [R, T, Hv*dv]`` and the log decays ``g`` and corrections
    ``beta`` ``[R, T, Hv]`` (all float32): ``chunked_delta_rule`` with value
    head ``h`` reading key head ``h // (Hv / Hk)``. ``unsupported`` says which
    shapes tile."""
    return _rule(q, k, v, g, beta, n_key_heads, chunk, jnp.dtype(operands), bool(interpret))
