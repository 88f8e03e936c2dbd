"""Scores, mask, softmax and values of sparse attention in one kernel pair.

What ``models/sparse_attention._attend_chunk`` does between the selection and
the output, for whole rows at once: ``logits = q k^T / sqrt(d)`` per head,
``where(mask)``, ``softmax``, ``probs @ v``, and the probabilities summed over
the heads (the indexer's KL target). In XLA every ``[heads, queries, keys]``
float32 block of logits and probabilities goes through HBM several times;
here a block lives in VMEM and only ``o``, the softmax's two statistics a
head and query (largest logit, 1 / sum of exponentials), and the head-summed
``[queries, keys]`` target leave the kernel.

The selection is an input (``mask [R, T, T]`` int8, shared by the heads,
causal: a query's keys are at or before it); the kernel decides nothing.

Forward, a grid step = one block of ``Q_BLOCK`` queries of one head against
its keys ``0 .. block's last query``, all of them resident: three sweeps over
key chunks inside VMEM (scores and their max; ``exp`` and its sum; the
normalised probabilities into the target and into ``probs @ v``), so the
softmax is the plain one and the probabilities enter their product
normalised, as XLA's path rounds them. Backward, one kernel: a block's
probabilities are recomputed from the forward's statistics by the forward's
arithmetic (``exp(s - max) / sum``: the same roundings), ``dS = P * (dP -
rowsum(dO * O)) / sqrt(d)``, ``dq`` leaves by query block, ``dk``/``dv``
accumulate in VMEM over a group's query heads and all query blocks. Key
chunks past a block's last query are never read.

Precision: ``q``, ``k``, ``v``, ``dO`` and the probabilities enter their
products in ``operands`` (bfloat16 on the TPU, what a default-precision
product rounds them to; float32 in the interpreter's tests, where XLA's
products are exact too), accumulation, scale, mask, max, ``exp``, sum and
normalisation are float32, and a masked key contributes exactly 0.

Layouts: queries run along the lanes. ``q``, ``o`` and their cotangents are
``[R, H*d, T]`` (a head's block ``[d, queries]``), mask and target ``[R,
keys, queries]``, so a score block is ``k [keys, d] @ q [d, queries]``, the
softmax reduces over sublanes, and every product is plain or ``a @ b.T``.
That is the order XLA's layout assignment gives the projections' outputs
``[R, T, H, d]`` on the TPU (tokens minor), so the caller's transposes are
bitcasts (tests/test_tpu_aot_compile.py holds the compiled layer to it);
``k``/``v`` come ``[R, T, G*d]`` and are transposed here where a product
wants them so (an eighth of ``q``'s bytes).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "sparse_attention"
# What one grid step may hold in VMEM, under the 16 MiB of scoped VMEM that
# Mosaic grants by default: vmem_bytes() is the blocks and scratch, the
# compiler adds the sweeps' temporaries (tests/test_tpu_aot_compile.py asks
# it at the budget's edge).
_VMEM_BUDGET = 14 << 20
Q_BLOCK = 128  # queries a grid step, forward
Q_BLOCK_BWD = 256
KEY_CHUNK, KEY_CHUNK_BWD = 1024, 512  # the widest sweep, forward and backward
_NEG = -1e30  # a masked logit: exp(_NEG - max) is exactly 0
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def key_chunk(tokens: int, widest: int) -> Optional[int]:
    """Keys a sweep takes at a time: the widest power of two from 128 to
    ``widest`` that cuts the row. On the v5e the forward's sweeps run 12%
    faster over 1,024 keys than over 512 (less loop overhead than keys
    wasted past the diagonal), the backward's five products 3% slower
    (PERF.md section 6, PR 29)."""
    return next((c for c in (1024, 512, 256, 128) if c <= widest and tokens % c == 0), None)


def vmem_bytes(tokens: int, head_dim: int) -> int:
    """VMEM of a grid step, the larger of the two kernels'. Forward: the
    row's ``k`` and ``v`` of one group (bfloat16) and the ``[tokens, Q_BLOCK]``
    blocks of mask (int8) and target (float32), double-buffered, and the
    score scratch of the same size. Backward: ``k``, its transpose and ``v``
    (bfloat16) and ``dk``, ``dv`` (float32), one buffer each, and the mask's
    double-buffered block. The query-sized blocks are counted with the
    sweeps' temporaries in the margin the budget leaves."""
    forward = 2 * 2 * tokens * head_dim * 2 + Q_BLOCK * tokens * (2 * 1 + 2 * 4 + 4)
    backward = tokens * head_dim * (3 * 2 + 2 * 4) + 2 * Q_BLOCK_BWD * tokens
    return max(forward, backward)


def unsupported(tokens: int, n_heads: int, n_kv_heads: int, head_dim: int) -> Optional[str]:
    """Why the kernels do not take this geometry, or None if they do."""
    if head_dim % 128:
        return f"head_dim {head_dim} is not a multiple of 128 lanes"
    if n_heads % n_kv_heads:
        return f"{n_heads} query heads do not group over {n_kv_heads} key-value heads"
    if tokens % Q_BLOCK_BWD:
        return f"{tokens} tokens a row do not cut into blocks of {Q_BLOCK_BWD} queries"
    need = vmem_bytes(tokens, head_dim)
    if need > _VMEM_BUDGET:
        return (f"{tokens} keys x head_dim {head_dim} need {need / 2**20:.1f} MiB "
                f"of VMEM a block (budget {_VMEM_BUDGET >> 20})")
    return None


def _chunks_under(block, queries: int, chunk: int):
    """Key chunks that hold a key at or before query block ``block``'s last."""
    return ((block + 1) * queries + chunk - 1) // chunk


def _fwd_kernel(q_ref, k_ref, vt_ref, mask_ref, o_ref, m_ref, inv_ref, tgt_ref, s_ref, *,
                scale: float, chunk: int):
    i, g, hh = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    d, queries = q_ref.shape
    n = _chunks_under(i, queries, chunk)

    @pl.when((g == 0) & (hh == 0))
    def _():
        tgt_ref[...] = jnp.zeros_like(tgt_ref)

    q = q_ref[...]

    def keys(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def scores(c, m):
        s = jnp.dot(k_ref[keys(c), :], q, preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask_ref[keys(c), :].astype(jnp.int32) != 0, s, _NEG)
        s_ref[c] = s
        return jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))

    m = lax.fori_loop(0, n, scores, jnp.full((1, queries), _NEG, jnp.float32))

    def exps(c, total):
        e = jnp.exp(s_ref[c] - m)
        s_ref[c] = e
        return total + jnp.sum(e, axis=0, keepdims=True)

    total = lax.fori_loop(0, n, exps, jnp.zeros((1, queries), jnp.float32))
    inv = 1.0 / total

    def values(c, acc):
        p = s_ref[c] * inv
        tgt_ref[keys(c), :] += p
        return acc + jnp.dot(vt_ref[:, keys(c)], p.astype(vt_ref.dtype),
                             preferred_element_type=jnp.float32)

    o_ref[...] = lax.fori_loop(0, n, values, jnp.zeros((d, queries), jnp.float32))
    m_ref[pl.ds(hh, 1), :] = m
    inv_ref[pl.ds(hh, 1), :] = inv


def _bwd_kernel(q_ref, k_ref, kt_ref, v_ref, mask_ref, m_ref, inv_ref, delta_ref, do_ref,
                dq_ref, dk_ref, dv_ref, *, scale: float, chunk: int):
    i, hh = pl.program_id(2), pl.program_id(3)
    d, queries = q_ref.shape
    n = _chunks_under(i, queries, chunk)

    @pl.when((i == 0) & (hh == 0))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    q = q_ref[...]
    do = do_ref[...].astype(q.dtype)
    head = pl.ds(hh, 1)
    m, inv, delta = m_ref[head, :], inv_ref[head, :], delta_ref[head, :]

    def body(c, dq):
        keys = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        s = jnp.dot(k_ref[keys, :], q, preferred_element_type=jnp.float32) * scale
        # the forward's probabilities, by the forward's own arithmetic
        p = jnp.where(mask_ref[keys, :].astype(jnp.int32) != 0, jnp.exp(s - m) * inv, 0.0)
        dp = jnp.dot(v_ref[keys, :], do, preferred_element_type=jnp.float32)
        # the logits' cotangent takes the 1/sqrt(d) before it is rounded, as
        # XLA's transpose of ``logits / sqrt(d)`` does
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dv_ref[keys, :] += lax.dot_general(p.astype(q.dtype), do, _NT,
                                           preferred_element_type=jnp.float32)
        dk_ref[keys, :] += lax.dot_general(ds, q, _NT, preferred_element_type=jnp.float32)
        return dq + jnp.dot(kt_ref[:, keys], ds, preferred_element_type=jnp.float32)

    dq_ref[...] = lax.fori_loop(0, n, body, jnp.zeros((d, queries), jnp.float32))


def _specs(d: int, per_group: int, tokens: int, queries: int, group_major: bool):
    """Block specs of a kernel whose grid is ``(row, query block, group, head
    of the group)`` or, ``group_major``, ``(row, group, query block, head)``:
    a head's ``[d, queries]`` block, a group's keys ``[tokens, d]`` and their
    transpose, the ``[tokens, queries]`` blocks all heads share, and a
    group's per-head statistics ``[per_group, queries]``."""
    if group_major:
        def at(f):
            return lambda r, g, i, hh: f(r, i, g, hh)
    else:
        def at(f):
            return f
    head = pl.BlockSpec((None, d, queries), at(lambda r, i, g, hh: (r, g * per_group + hh, i)))
    keys = functools.partial(pl.BlockSpec, (None, tokens, d), at(lambda r, i, g, hh: (r, 0, g)))
    keys_t = functools.partial(pl.BlockSpec, (None, d, tokens), at(lambda r, i, g, hh: (r, g, 0)))
    wide = pl.BlockSpec((None, tokens, queries), at(lambda r, i, g, hh: (r, 0, i)))
    stat = pl.BlockSpec((None, None, per_group, queries), at(lambda r, i, g, hh: (r, g, 0, i)))
    return head, keys, keys_t, wide, stat


def _geometry(q_t, k, n_heads: int, widest_chunk: int):
    R, width, T = q_t.shape
    d = width // n_heads
    G = k.shape[2] // d
    return R, T, d, G, n_heads // G, key_chunk(T, widest_chunk)


def _forward_call(q_t, k, v_t, mask_t, *, n_heads: int, interpret: bool):
    """``(o_t [R, H*d, T], each head's and query's largest logit and 1 / sum
    of exponentials, both [R, G, H/G, T], sum over the heads of the
    probabilities [R, keys, queries])``, all float32."""
    R, T, d, G, per_group, chunk = _geometry(q_t, k, n_heads, KEY_CHUNK)
    head, keys, keys_t, wide, stat = _specs(d, per_group, T, Q_BLOCK, group_major=False)
    pairs = R * n_heads * T * T // 2  # query-key pairs under the diagonal
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d), chunk=chunk),
        grid=(R, T // Q_BLOCK, G, per_group),
        in_specs=[head, keys(), keys_t(), wide],
        out_specs=[head, stat, stat, wide],
        out_shape=[
            jax.ShapeDtypeStruct(q_t.shape, jnp.float32),
            jax.ShapeDtypeStruct((R, G, per_group, T), jnp.float32),
            jax.ShapeDtypeStruct((R, G, per_group, T), jnp.float32),
            jax.ShapeDtypeStruct((R, T, T), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((T // chunk, chunk, Q_BLOCK), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * pairs * d, transcendentals=pairs,
            bytes_accessed=(q_t.size + 2 * k.size * (T // Q_BLOCK)) * q_t.dtype.itemsize
            + 4 * q_t.size + 5 * mask_t.size,
        ),
        interpret=interpret,
        name=NAME + "_fwd",
    )(q_t, k, v_t, mask_t)


def _backward_call(q_t, k, k_t, v, mask_t, m, inv, delta, do_t, *, n_heads: int,
                   interpret: bool):
    """``(dq_t [R, H*d, T], dk, dv [R, T, G*d])``, float32."""
    R, T, d, G, per_group, chunk = _geometry(q_t, k, n_heads, KEY_CHUNK_BWD)
    head, keys, keys_t, wide, stat = _specs(d, per_group, T, Q_BLOCK_BWD, group_major=True)
    # one buffer: a group's keys change once in (T / Q_BLOCK_BWD) * per_group steps
    once = dict(pipeline_mode=pl.Buffered(1))
    pairs = R * n_heads * T * T // 2
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=1.0 / math.sqrt(d), chunk=chunk),
        grid=(R, G, T // Q_BLOCK_BWD, per_group),
        in_specs=[head, keys(**once), keys_t(**once), keys(**once), wide, stat, stat, stat, head],
        out_specs=[head, keys(**once), keys(**once)],
        out_shape=[
            jax.ShapeDtypeStruct(q_t.shape, jnp.float32),
            jax.ShapeDtypeStruct(k.shape, jnp.float32),
            jax.ShapeDtypeStruct(k.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=10 * pairs * d, transcendentals=pairs,
            bytes_accessed=q_t.size * q_t.dtype.itemsize + 8 * q_t.size + G * mask_t.size
            + 11 * k.size,
        ),
        interpret=interpret,
        name=NAME + "_bwd",
    )(q_t, k, k_t, v, mask_t, m, inv, delta, do_t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attend(q_t, k, v, mask_t, n_heads, operands, interpret):
    return _attend_fwd(q_t, k, v, mask_t, n_heads, operands, interpret)[0]


def _attend_fwd(q_t, k, v, mask_t, n_heads, operands, interpret):
    q_t, k, v = (t.astype(operands) for t in (q_t, k, v))
    o_t, m, inv, summed = _forward_call(q_t, k, v.swapaxes(1, 2), mask_t,
                                        n_heads=n_heads, interpret=interpret)
    return (o_t, summed / n_heads), (q_t, k, v, mask_t, o_t, m, inv)


def _attend_bwd(n_heads, operands, interpret, res, cts):
    q_t, k, v, mask_t, o_t, m, inv = res
    do_t = cts[0]  # the target is a statistic for the indexer: no cotangent
    R, G, per_group, T = m.shape
    # rowsum(dO * O) a head, of dO as it enters its products
    delta = jnp.sum((do_t.astype(operands).astype(jnp.float32) * o_t)
                    .reshape(R, G, per_group, -1, T), axis=3)
    dq_t, dk, dv = _backward_call(q_t, k, k.swapaxes(1, 2), v, mask_t, m, inv, delta, do_t,
                                  n_heads=n_heads, interpret=interpret)
    return dq_t, dk, dv, None


_attend.defvjp(_attend_fwd, _attend_bwd)


def attend(q_t, k, v, mask_t, *, n_heads: int, operands=jnp.bfloat16,
           interpret: bool = False):
    """``(o_t [R, H*d, T], target_t [R, T, T])`` float32 of ``q_t [R, H*d,
    T]``, ``k``/``v`` ``[R, T, G*d]`` (float32) under ``mask_t [R, keys,
    queries]`` (int8, nonzero = selected; nothing after the query): ``o_t``
    the heads' outputs, ``target_t [R, keys, queries]`` the probabilities
    summed over the heads and divided by ``H``, which carries no gradient.
    ``unsupported`` says which shapes tile."""
    o_t, target_t = _attend(q_t, k, v, mask_t, n_heads, jnp.dtype(operands), bool(interpret))
    return o_t, lax.stop_gradient(target_t)
