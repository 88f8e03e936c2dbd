"""One backward kernel for the tail of a Bottleneck block.

The tail is ``relu(bn2(z)) -> 1x1 conv (C -> 4C) -> bn3`` with batch
statistics. XLA never stores the gradient of the conv's output: it is
``g = k*dy + c1*x3 + c0`` per channel (batch norm's backward), recomputed from
the wide cotangent ``dy`` and the wide pre-BN activation ``x3`` inside BOTH
the input-gradient and the weight-gradient fusion, because two convolutions
cannot share a fusion. Each of the two reads ``dy`` and ``x3`` (the two
largest tensors of the block) once. Here the forward stays XLA's, op for op,
and the backward is one Pallas kernel, a spatial position a grid step, that
forms ``g`` once and feeds both products from it: ``dz`` (through ``w``
transposed, the ReLU mask and bn2's scale) and ``dw``, plus the two
per-channel sums from which bn2's own backward follows by ordinary autodiff
outside. A site whose block of all rows does not fit the VMEM budget stays
on XLA's backward (``unsupported``).

Precision is that of XLA's path on the TPU: ``g``, ``a = relu(bn2(z))`` and
``w`` are rounded to bfloat16 where a default-precision convolution rounds
its operands, products accumulate in float32.

Two operand orders, by what XLA's conv fusions choose for an NHWC activation
on the TPU, so that the kernel's operands and results are bitcasts of theirs:
under 128 channels the batch is the minor dimension (``[H*W, C, N]``), from
128 channels on the channels are (``[H*W, N, C]``). The wide side (4C >= 128
wherever the kernel engages) is always ``[H*W, N, 4C]``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What one block of the kernel may hold in VMEM, under the 16 MiB of scoped
# VMEM that Mosaic grants by default: its own count was vmem_bytes() plus the
# per-channel vectors (under 50 KiB) at every geometry asked of the v5e's
# compiler (tests/test_pointwise_bwd.py), so the margin is for other
# versions of it.
_VMEM_BUDGET = 14 << 20


def rows_minor(channels: int) -> bool:
    """Whether the narrow side goes in as ``[H*W, C, N]`` (batch minor)."""
    return channels < 128


def vmem_bytes(rows: int, channels: int, wide: int) -> int:
    """VMEM of one grid step: a block is all rows of one spatial position,
    ``dy``, ``x3``, ``z`` and ``dz`` double-buffered in float32; ``dw`` (float32)
    and ``w`` (bfloat16) stay for the whole call."""
    return 16 * rows * (wide + channels) + 6 * channels * wide


def unsupported(rows: int, channels: int, wide: int) -> Optional[str]:
    """Why the kernel does not take this site, or None if it does. ``rows`` is
    the batch, ``channels`` the conv's input width, ``wide`` its output's."""
    if wide % 128:
        return f"{wide} output channels are not a multiple of 128"
    if rows_minor(channels):
        if channels % 16 or rows % 128:
            return (f"{channels} channels x {rows} rows do not tile "
                    "[16, 128] (batch-minor order)")
    elif channels % 128 or rows % 16:
        return (f"{rows} rows x {channels} channels do not tile [16, 128]")
    need = vmem_bytes(rows, channels, wide)
    if need > _VMEM_BUDGET:
        return (f"{rows} rows x ({channels} -> {wide}) channels need "
                f"{need / 2**20:.1f} MiB of VMEM a block "
                f"(budget {_VMEM_BUDGET >> 20})")
    return None


def batch_moments(x):
    """``(mean, biased variance)`` over all but the channels, float32, as
    ``CrossReplicaBatchNorm`` takes them: mean of squares less squared mean."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    return mean, jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)


def _forward(z, mean2, inv2, scale2, bias2, w, scale3, bias3, eps):
    """The ops ``CrossReplicaBatchNorm`` / ``nn.relu`` / ``nn.Conv`` run, in
    their order, so that the routed forward is the unrouted one bit for bit."""
    a = jax.nn.relu((z - mean2) * inv2 * scale2 + bias2)
    x3 = lax.conv_general_dilated(
        a, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    mean3, var3 = batch_moments(x3)
    inv3 = lax.rsqrt(var3 + eps)
    y = (x3 - mean3) * inv3 * scale3 + bias3
    return y, mean3, var3, x3, inv3


def _kernel(gv_ref, zv_ref, w_ref, dy_ref, x3_ref, z_ref,
            dz_ref, dw_ref, sums_ref, *, minor: bool):
    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    g = gv_ref[0] * dy_ref[...] + gv_ref[1] * x3_ref[...] + gv_ref[2]
    gb = g.astype(jnp.bfloat16)  # [rows, 4C]
    inv2, scale2 = zv_ref[1], zv_ref[2]
    zc = z_ref[...] - zv_ref[0]
    u = zc * inv2 * scale2 + zv_ref[3]
    a = jnp.maximum(u, 0.0).astype(jnp.bfloat16)
    if minor:  # z is [C, rows], w is [C, 4C]
        da = lax.dot_general(
            w_ref[...], gb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dw = jnp.dot(a, gb, preferred_element_type=jnp.float32)
    else:  # z is [rows, C], w is transposed: [4C, C]
        da = jnp.dot(gb, w_ref[...], preferred_element_type=jnp.float32)
        dw = lax.dot_general(
            a, gb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    du = jnp.where(u > 0.0, da, 0.0)
    dz_ref[...] = du * (inv2 * scale2)
    dw_ref[...] += dw
    over_rows = 1 if minor else 0
    sums_ref[0] += jnp.sum(du, axis=over_rows, keepdims=True)
    sums_ref[1] += jnp.sum(du * zc, axis=over_rows, keepdims=True)


def _backward_call(gv, zv, w, dy, x3, z, *, minor: bool, interpret: bool):
    """``dz`` (in ``z``'s order), ``dw [C, 4C]`` and the two sums
    ``[2, C, 1]`` or ``[2, 1, C]`` (of ``du`` and of ``du * (z - mean2)``,
    ``du`` the cotangent of bn2's output under the ReLU mask)."""
    positions, rows, wide = dy.shape
    channels = z.shape[1] if minor else z.shape[2]

    def full(shape):
        return pl.BlockSpec(shape, lambda p: (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    def position(shape):
        return pl.BlockSpec((None,) + shape, lambda p: (p, 0, 0),
                            memory_space=pltpu.VMEM)

    wide_spec = position((rows, wide))
    z_spec = position(z.shape[1:])
    return pl.pallas_call(
        functools.partial(_kernel, minor=minor),
        grid=(positions,),
        in_specs=[full(gv.shape), full(zv.shape), full(w.shape),
                  wide_spec, wide_spec, z_spec],
        out_specs=[z_spec, full((channels, wide)), full((2,) + zv.shape[1:])],
        out_shape=[
            jax.ShapeDtypeStruct(z.shape, jnp.float32),
            jax.ShapeDtypeStruct((channels, wide), jnp.float32),
            jax.ShapeDtypeStruct((2,) + zv.shape[1:], jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * positions * rows * channels * wide,
            bytes_accessed=4 * positions * rows * 2 * (wide + channels),
            transcendentals=0,
        ),
        interpret=interpret,
        name="pointwise_bwd",
    )(gv, zv, w, dy, x3, z)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _expand_conv_bn(z, mean2, inv2, scale2, bias2, w, scale3, bias3,
                    eps, interpret):
    return _forward(z, mean2, inv2, scale2, bias2, w, scale3, bias3, eps)[:3]


def _fwd(z, mean2, inv2, scale2, bias2, w, scale3, bias3, eps, interpret):
    y, mean3, var3, x3, inv3 = _forward(
        z, mean2, inv2, scale2, bias2, w, scale3, bias3, eps
    )
    # nothing of activation size beyond z and x3, which XLA's path keeps too
    res = (z, mean2, inv2, scale2, bias2, w, x3, mean3, inv3, scale3)
    return (y, mean3, var3), res


def _bn3_backward(dy, x3, mean3, inv3, scale3):
    """``(dbias3, dscale3, [k, c1, c0])``: bn3's parameter gradients and the
    per-channel constants of ``g = k*dy + c1*x3 + c0``, the gradient of the
    conv's output. The two reductions fuse into whatever produces ``dy``."""
    count = dy.size // dy.shape[-1]
    sum_dy = jnp.sum(dy, axis=(0, 1, 2))
    sum_dyx = jnp.sum(dy * (x3 - mean3), axis=(0, 1, 2))
    dscale3 = sum_dyx * inv3
    k = scale3 * inv3
    c1 = -k * inv3 * dscale3 / count
    c0 = -k * sum_dy / count - c1 * mean3
    return sum_dy, dscale3, jnp.stack([k, c1, c0]).reshape(3, 1, -1)


def _bwd(eps, interpret, res, cts):
    z, mean2, inv2, scale2, bias2, w, x3, mean3, inv3, scale3 = res
    dy = cts[0]  # the batch moments feed running statistics only
    n, h, wd, c = z.shape
    wide = x3.shape[-1]
    sum_dy, dscale3, gv = _bn3_backward(dy, x3, mean3, inv3, scale3)
    # The barrier keeps dy an NHWC value of its producer (a conv fusion that
    # applies the block's ReLU mask as it writes, and takes the two sums
    # above along): without it XLA moves the kernel's reshape up through the
    # mask's select, and the select becomes a pass of its own.
    dy = lax.optimization_barrier(dy)

    def wide_order(t):
        return t.transpose(1, 2, 0, 3).reshape(h * wd, n, wide)

    minor = rows_minor(c)
    zv = jnp.stack([mean2, inv2, scale2, bias2])
    w2 = w.reshape(c, wide).astype(jnp.bfloat16)
    if minor:
        zk = z.transpose(1, 2, 3, 0).reshape(h * wd, c, n)
        zv = zv.reshape(4, c, 1)
    else:
        zk = z.transpose(1, 2, 0, 3).reshape(h * wd, n, c)
        zv = zv.reshape(4, 1, c)
        w2 = w2.T
    dzk, dw, sums = _backward_call(
        gv, zv, w2, wide_order(dy), wide_order(x3), zk, minor=minor,
        interpret=interpret,
    )
    if minor:
        dz = dzk.reshape(h, wd, c, n).transpose(3, 0, 1, 2)
    else:
        dz = dzk.reshape(h, wd, n, c).transpose(2, 0, 1, 3)
    s0, s1 = sums.reshape(2, c)
    return (
        dz,
        -inv2 * scale2 * s0,  # mean2
        scale2 * s1,  # inv2
        inv2 * s1,  # scale2
        s0,  # bias2
        dw.reshape(w.shape),
        dscale3,
        sum_dy,  # bias3
    )


_expand_conv_bn.defvjp(_fwd, _bwd)


def expand_conv_bn(z, mean2, inv2, scale2, bias2, w, scale3, bias3, *,
                   eps: float, interpret: bool = False):
    """``(y, batch_mean3, batch_var3)`` of ``bn3(conv1x1(relu(bn2(z))))``.

    ``z`` is the 3x3 conv's output ``[N, H, W, C]`` (float32), ``mean2`` and
    ``inv2 = rsqrt(var2 + eps)`` its batch statistics (``batch_moments``),
    computed by the caller so that their own backward stays autodiff's; ``w`` is the
    ``[1, 1, C, 4C]`` kernel. The batch moments of the conv's output come back
    for the running statistics and carry no gradient. ``unsupported`` says
    which shapes the backward kernel tiles.
    """
    y, mean3, var3 = _expand_conv_bn(
        z, mean2, inv2, scale2, bias2, w, scale3, bias3, float(eps),
        bool(interpret),
    )
    return y, lax.stop_gradient(mean3), lax.stop_gradient(var3)
