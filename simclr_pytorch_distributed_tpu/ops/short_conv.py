"""The Gated DeltaNet layer's causal depthwise convolution and its ``silu``
in one Mosaic kernel pair.

What ``jax.nn.silu(models/gated_delta.short_conv(x, w))`` computes, per
row of ``T`` tokens and channel ``c``: ``u[t] = sum_j w[j] x[t - K + 1 + j]``
with zeros before the first token, the taps summed in float32 in the order
``j = 0 .. K - 1``, and ``y = silu(u)``. In XLA the forward fusion reads the
padded input once a tap, and autodiff of the pad and the ``K`` shifted
slices writes ``K`` padded contributions to ``dx`` and makes ``K``
reductions for ``dw``, each over ``dy`` and ``x`` again. Here each pass
reads its inputs once and writes its outputs once.

``x`` is read where it lies: the first ``C`` columns of the layer's
projection ``[R, T, width]`` (``C = w.shape[1]``; the rest is ``z``, which
the kernels never touch), through a ``BlockSpec`` whose channel blocks stay
inside them, so no slice of the projection is made.

Forward: a grid step is a block of ``TOKEN_BLOCK`` tokens of one channel
block of one row; a row's token blocks run in order on the innermost
("arbitrary") grid axis and a VMEM scratch carries the last 8 tokens of
``x`` from one to the next (the taps reach ``K - 1`` of them), zero at the
row's start. Within a block the tokens go ``_SLAB`` at a time: the slab
under the 8 tokens before it, rolled down by each tap's distance.

Backward, one kernel: the token blocks in reverse. It recomputes ``u`` from
``x`` (the 8 tokens before a block come through a second, 8-token
``BlockSpec`` on the same operand, clamped and masked to zero at the row's
start), ``du = dy * silu'(u)``, ``dx[s] = sum_j w[j] du[s + K - 1 - j]``
with the first 8 tokens of the later block's ``du`` carried in VMEM (zero
past the row's end), and ``dw[j] = sum_t du[t] x[t - K + 1 + j]``
accumulated in VMEM as ``[K, 8, channels]`` partial sums and written once a
row and channel block; XLA sums the rows' ``[R, K, C]`` partials.

Nothing is kept for the backward but its inputs: the projection (which the
caller holds already) and ``w``. Everything is float32.
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

NAME = "short_conv"
TOKEN_BLOCK = 512  # tokens a grid step
CHANNEL_BLOCKS = (512, 256, 128)  # the widest that cuts the channels is taken
_SLAB = 32  # tokens a block handles at once
_HALO = 8  # tokens before a block that the taps may reach: one sublane tile


def channel_block(channels: int) -> Optional[int]:
    """Channels a grid step: the widest of ``CHANNEL_BLOCKS`` that cuts
    ``channels``, None where none does."""
    return next((c for c in CHANNEL_BLOCKS if channels % c == 0), None)


def vmem_bytes(channels: int, taps: int) -> int:
    """VMEM of a backward grid step, which holds more than a forward one:
    the blocks of ``x``, ``dy`` and ``dx``, ``x``'s 8 tokens before the
    block, ``w`` and the ``dw`` partials, each double-buffered, the carried
    ``du`` and the ``[K, 8, channels]`` accumulator. Mosaic counts the same
    to the byte (tests/test_tpu_aot_compile.py asks it); the slabs'
    temporaries live in registers."""
    cb = channel_block(channels)
    blocks = 2 * (3 * TOKEN_BLOCK + _HALO + 2 * taps) * cb
    return 4 * (blocks + (1 + taps) * _HALO * cb)


def unsupported(tokens: int, channels: int, taps: int, dtype) -> Optional[str]:
    """Why the kernels do not take this geometry, or None if they do."""
    if jnp.dtype(dtype) != jnp.float32:
        return f"compute dtype {jnp.dtype(dtype).name}"
    if channel_block(channels) is None:
        return f"{channels} channels are not a multiple of 128 lanes"
    if not 1 <= taps <= _HALO + 1:
        return f"{taps} taps reach past the {_HALO} tokens a block is given"
    if tokens % TOKEN_BLOCK:
        return f"{tokens} tokens a row do not cut into blocks of {TOKEN_BLOCK}"
    need = vmem_bytes(channels, taps)
    if need > _VMEM_BUDGET:
        return (f"blocks of {TOKEN_BLOCK} tokens x {channel_block(channels)} channels need "
                f"{need / 2**20:.1f} MiB of VMEM a step (budget {_VMEM_BUDGET >> 20})")
    return None


def _behind(before, cur, s: int):
    """``cur [S, c]`` moved ``s`` tokens later, ``before``'s last tokens
    moving in: row ``r`` holds ``cur[r - s]``, or ``before[8 + r - s]``
    where ``r < s``."""
    if s == 0:
        return cur
    return pltpu.roll(jnp.concatenate([before, cur], axis=0), s, 0)[_HALO:]


def _ahead(cur, after, s: int):
    """``cur [S, c]`` moved ``s`` tokens earlier, ``after``'s first tokens
    moving in: row ``r`` holds ``cur[r + s]``, or ``after[r + s - S]``."""
    if s == 0:
        return cur
    rows = cur.shape[0] + _HALO
    return pltpu.roll(jnp.concatenate([cur, after], axis=0), rows - s, 0)[:cur.shape[0]]


def _pre_activation(w, before, cur, taps: int):
    """``u`` of a slab and ``x`` moved by each distance ``0 .. K - 1``;
    the taps summed in ``short_conv``'s order."""
    moved = [_behind(before, cur, s) for s in range(taps)]
    u = moved[taps - 1] * w[0:1]
    for j in range(1, taps):
        u = u + moved[taps - 1 - j] * w[j:j + 1]
    return u, moved


def _fwd_kernel(w_ref, x_ref, y_ref, carry_ref, *, taps: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    w = w_ref[...]

    def slab(i, before):
        start = pl.multiple_of(i * _SLAB, _SLAB)
        cur = x_ref[pl.ds(start, _SLAB), :]
        u, _ = _pre_activation(w, before, cur, taps)
        y_ref[pl.ds(start, _SLAB), :] = jax.nn.silu(u)
        return cur[_SLAB - _HALO:]

    carry_ref[...] = lax.fori_loop(0, x_ref.shape[0] // _SLAB, slab, carry_ref[...])


def _bwd_kernel(w_ref, x_ref, halo_ref, dy_ref, dx_ref, dw_ref, carry_ref, acc_ref, *,
                taps: int):
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    # x's 8 tokens before this block: none before the row's first
    head = jnp.where(step == steps - 1, 0.0, halo_ref[...])
    slabs = x_ref.shape[0] // _SLAB

    def slab(k, after):
        i = slabs - 1 - k
        start = pl.multiple_of(i * _SLAB, _SLAB)
        cur = x_ref[pl.ds(start, _SLAB), :]
        inside = x_ref[pl.ds(pl.multiple_of(jnp.maximum(start - _HALO, 0), _HALO), _HALO), :]
        u, moved = _pre_activation(w, jnp.where(i == 0, head, inside), cur, taps)
        sig = jax.nn.sigmoid(u)
        du = dy_ref[pl.ds(start, _SLAB), :] * (sig * (1.0 + u * (1.0 - sig)))
        dx = _ahead(du, after, taps - 1) * w[0:1]
        for j in range(1, taps):
            dx = dx + _ahead(du, after, taps - 1 - j) * w[j:j + 1]
        dx_ref[pl.ds(start, _SLAB), :] = dx
        for j in range(taps):
            part = du * moved[taps - 1 - j]
            acc_ref[j] += sum(part[m:m + _HALO] for m in range(0, _SLAB, _HALO))
        return du[:_HALO]

    carry_ref[...] = lax.fori_loop(0, slabs, slab, carry_ref[...])

    @pl.when(step == steps - 1)
    def _():
        dw_ref[...] = jnp.sum(acc_ref[...], axis=1)


def _grid(x, w):
    R, T, _ = x.shape
    taps, channels = w.shape
    cb = channel_block(channels)
    return R, T, taps, channels, cb, (R, channels // cb, T // TOKEN_BLOCK)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward_call(x, w, *, interpret: bool):
    """``silu(u) [R, T, C]`` float32 of the first ``C`` columns of ``x``."""
    R, T, taps, channels, cb, grid = _grid(x, w)
    block = pl.BlockSpec((None, TOKEN_BLOCK, cb), lambda r, c, t: (r, t, c))
    elements = R * T * channels
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps),
        grid=grid,
        in_specs=[pl.BlockSpec((taps, cb), lambda r, c, t: (0, c)), block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((R, T, channels), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_HALO, cb), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(flops=2 * taps * elements, transcendentals=elements,
                                      bytes_accessed=4 * (2 * elements + w.size)),
        interpret=interpret,
        name=NAME + "_fwd",
    )(w, x)


def _backward_call(x, w, dy, *, interpret: bool):
    """``(dx [R, T, C], dw [R, K, C])`` float32: ``dw`` a row's share."""
    R, T, taps, channels, cb, grid = _grid(x, w)
    blocks = grid[2]
    back = lambda t: blocks - 1 - t  # noqa: E731
    block = pl.BlockSpec((None, TOKEN_BLOCK, cb), lambda r, c, t: (r, back(t), c))
    # the 8 tokens before the block, in blocks of 8 (the row's first is masked)
    halo = pl.BlockSpec(
        (None, _HALO, cb),
        lambda r, c, t: (r, jnp.maximum(back(t) * (TOKEN_BLOCK // _HALO) - 1, 0), c))
    taps_block = pl.BlockSpec((taps, cb), lambda r, c, t: (0, c))
    elements = R * T * channels
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps),
        grid=grid,
        in_specs=[taps_block, block, halo, block],
        out_specs=[block, pl.BlockSpec((None, taps, cb), lambda r, c, t: (r, 0, c))],
        out_shape=[jax.ShapeDtypeStruct((R, T, channels), jnp.float32),
                   jax.ShapeDtypeStruct((R, taps, channels), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_HALO, cb), jnp.float32),
                        pltpu.VMEM((taps, _HALO, cb), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(flops=6 * taps * elements, transcendentals=elements,
                                      bytes_accessed=4 * (3 * elements + (R + 1) * w.size)),
        interpret=interpret,
        name=NAME + "_bwd",
    )(w, x, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv(x, w, interpret):
    return _forward_call(x, w, interpret=interpret)


def _conv_fwd(x, w, interpret):
    return _forward_call(x, w, interpret=interpret), (x, w)


def _conv_bwd(interpret, res, dy):
    x, w = res
    dx, dw = _backward_call(x, w, dy, interpret=interpret)
    # zeros for the columns past C: the compiler folds the pad, with z's
    # cotangent, into the products that take the projection's cotangent
    return jnp.pad(dx, ((0, 0), (0, 0), (0, x.shape[-1] - dx.shape[-1]))), jnp.sum(dw, axis=0)


_conv.defvjp(_conv_fwd, _conv_bwd)


def short_conv_silu(x, w, *, interpret: bool = False):
    """``jax.nn.silu(gated_delta.short_conv(x[..., :C], w))`` ``[R, T, C]``
    float32 for ``x [R, T, width]`` and ``w [K, C]`` (float32, ``C <=
    width``). ``unsupported`` says which shapes tile."""
    return _conv(x, w, bool(interpret))
