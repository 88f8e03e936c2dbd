"""Fused Pallas TPU kernel for the SupCon/SimCLR contrastive loss.

The reference materializes the full ``[V*B, V*B]`` logits matrix and three more
same-sized temporaries (mask, exp_logits, log_prob — reference ``losses.py:64-90``),
all round-tripping through HBM. This kernel is the flash-attention-style
decomposition of the same math: the logits tile ``[bm, bn]`` lives only in VMEM,
a numerically exact online log-sum-exp streams over column blocks, and the
positive-pair similarities accumulate alongside. HBM traffic drops from
O((VB)^2) to O(VB·D), and the row-max subtraction (``losses.py:68-69``) is
replaced by the online max, which cancels exactly in ``logit − logsumexp``.

Semantics match ``ops.losses.supcon_loss`` (contrast_mode='all') bit-for-fp32:
the τ/τ_base final scale, self-pair exclusion, and the mean over all V·B anchor
rows. Both SimCLR (positives = other views of the same sample) and SupCon
(positives = same label) reduce to one code path by comparing per-row integer
ids (sample index or label).

The backward pass is a second Pallas kernel. With symmetric logits
``L = F·Fᵀ/τ``, the gradient is ``dF = g·(G + Gᵀ)·F/τ`` where
``G_ij = c·(softmax_ij − P_ij/cnt_i)``, ``c = (τ/τ_base)/(V·B)``; the kernel
recomputes each logits tile (no O(N²) residual is ever stored — only the
per-row ``lse`` and positive counts) and contracts both terms against the
column features in one pass.

Sharded mode (``fused_sharded_supcon_loss``): the same kernels run inside
``shard_map`` over the ``data`` mesh axis. Anchor rows stay sharded (each
device owns ``m = V·B/P`` contiguous view-major rows, the layout the reference
assembles post-gather, ``main_supcon.py:276-279``); the contrast side is the
all-gathered ``[V·B, D]`` feature matrix — the same O(V·B·D) replicated
transfer the reference's NCCL ``all_gather`` performs (``main_supcon.py:268``)
— but the ``[m, V·B]`` logits block and its softmax temporaries never touch
HBM. The grid is rectangular (local rows × global cols); self/positive masking
uses explicit global row/col indices instead of ``program_id`` so a shard's
row offset is a traced value. The backward exploits logits symmetry: row i's
full gradient ``(G + Gᵀ)_i,: · F`` needs only row-i softmax stats (local) and
col-j stats (the all-gathered O(V·B) ``lse``/``cnt`` vectors), so each device
computes the exact global gradient of its own rows with no O(N²) residual.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _pick_block(n: int, cap: int) -> Optional[int]:
    for c in (512, 256, 128, 64, 32, 16, 8):
        if c <= cap and c <= n and n % c == 0:
            return c
    return None


def _vmem_spec(block_shape=None, index_map=None):
    if block_shape is None:
        return pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _fwd_kernel(
    frow_ref, fcol_ref, idr_ref, idc_ref, gr_ref, gc_ref,
    loss_ref, lse_ref, cnt_ref,
    m_sc, s_sc, p_sc, c_sc,
    *, bm: int, bn: int, inv_temp: float, scale: float,
):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_sc[:] = jnp.full((bm, 1), _NEG_INF, jnp.float32)
        s_sc[:] = jnp.zeros((bm, 1), jnp.float32)
        p_sc[:] = jnp.zeros((bm, 1), jnp.float32)
        c_sc[:] = jnp.zeros((bm, 1), jnp.float32)

    logits = (
        jnp.dot(frow_ref[:], fcol_ref[:].T, preferred_element_type=jnp.float32)
        * inv_temp
    )
    # global row/col ids come in as data (not program_id): in sharded mode the
    # row block's global offset is a traced per-device value.
    self_mask = gr_ref[:] == gc_ref[:]
    pos_mask = (idr_ref[:] == idc_ref[:]) & jnp.logical_not(self_mask)

    masked = jnp.where(self_mask, _NEG_INF, logits)
    blk_max = jnp.max(masked, axis=1, keepdims=True)
    new_max = jnp.maximum(m_sc[:], blk_max)
    s_sc[:] = s_sc[:] * jnp.exp(m_sc[:] - new_max) + jnp.sum(
        jnp.exp(masked - new_max), axis=1, keepdims=True
    )
    m_sc[:] = new_max
    p_sc[:] = p_sc[:] + jnp.sum(
        jnp.where(pos_mask, logits, 0.0), axis=1, keepdims=True
    )
    c_sc[:] = c_sc[:] + jnp.sum(pos_mask.astype(jnp.float32), axis=1, keepdims=True)

    @pl.when(j == nj - 1)
    def _():
        lse = m_sc[:] + jnp.log(s_sc[:])
        lse_ref[:] = lse
        cnt_ref[:] = c_sc[:]
        loss_ref[:] = -scale * (p_sc[:] / c_sc[:] - lse)


def _bwd_kernel(
    frow_ref, fcol_ref, idr_ref, idc_ref, gr_ref, gc_ref,
    lse_r_ref, lse_c_ref, cnt_r_ref, cnt_c_ref,
    dfeat_ref, acc_sc,
    *, bm: int, bn: int, inv_temp: float, coeff: float,
):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        acc_sc[:] = jnp.zeros_like(acc_sc)

    logits = (
        jnp.dot(frow_ref[:], fcol_ref[:].T, preferred_element_type=jnp.float32)
        * inv_temp
    )
    self_mask = gr_ref[:] == gc_ref[:]
    pos = ((idr_ref[:] == idc_ref[:]) & jnp.logical_not(self_mask)).astype(
        jnp.float32
    )

    # softmax terms for row-anchored (G) and column-anchored (Gᵀ) halves; both
    # use exp(l − lse) ≤ 1 since lse ≥ row max — no overflow.
    sm_i = jnp.where(self_mask, 0.0, jnp.exp(logits - lse_r_ref[:]))
    sm_j = jnp.where(self_mask, 0.0, jnp.exp(logits - lse_c_ref[:]))
    h = (sm_i - pos / cnt_r_ref[:]) + (sm_j - pos / cnt_c_ref[:])
    acc_sc[:] = acc_sc[:] + jnp.dot(
        h, fcol_ref[:], preferred_element_type=jnp.float32
    ) * (coeff * inv_temp)

    @pl.when(j == nj - 1)
    def _():
        dfeat_ref[:] = acc_sc[:]


def _fwd_call(
    frow, fcol, idr, idc, grow, gcol,
    temperature, base_temperature, interpret, bm, bn, vma=None,
):
    """Rectangular forward: per-row loss/lse/cnt for anchor rows ``frow``
    against contrast columns ``fcol`` (``frow is fcol`` in the dense case).

    ``vma`` is the varying-manual-axes set for the outputs when called inside
    shard_map (required by check_vma); ``None`` outside shard_map.
    """
    nr, d = frow.shape
    nc = fcol.shape[0]
    grid = (nr // bm, nc // bn)
    scale = temperature / base_temperature
    kernel = functools.partial(
        _fwd_kernel, bm=bm, bn=bn, inv_temp=1.0 / temperature, scale=scale
    )
    out_shape = [jax.ShapeDtypeStruct((nr, 1), jnp.float32, vma=vma)] * 3
    scratch = [pltpu.VMEM((bm, 1), jnp.float32) for _ in range(4)]
    row_spec = _vmem_spec((bm, 1), lambda i, j: (i, 0))
    col_spec = _vmem_spec((1, bn), lambda i, j: (0, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _vmem_spec((bm, d), lambda i, j: (i, 0)),
            _vmem_spec((bn, d), lambda i, j: (j, 0)),
            row_spec, col_spec, row_spec, col_spec,
        ],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(frow, fcol, idr[:, None], idc[None, :], grow[:, None], gcol[None, :])


def _bwd_call(
    frow, fcol, idr, idc, grow, gcol, lse_r, lse_c, cnt_r, cnt_c,
    temperature, coeff, interpret, bm, bn, vma=None,
):
    """Rectangular backward: exact global gradient of the anchor rows."""
    nr, d = frow.shape
    nc = fcol.shape[0]
    grid = (nr // bm, nc // bn)
    kernel = functools.partial(
        _bwd_kernel, bm=bm, bn=bn, inv_temp=1.0 / temperature, coeff=coeff
    )
    scratch = [pltpu.VMEM((bm, d), jnp.float32)]
    row_spec = _vmem_spec((bm, 1), lambda i, j: (i, 0))
    col_spec = _vmem_spec((1, bn), lambda i, j: (0, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _vmem_spec((bm, d), lambda i, j: (i, 0)),
            _vmem_spec((bn, d), lambda i, j: (j, 0)),
            row_spec, col_spec, row_spec, col_spec,
            row_spec, col_spec, row_spec, col_spec,
        ],
        out_specs=_vmem_spec((bm, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nr, d), jnp.float32, vma=vma),
        interpret=interpret,
        scratch_shapes=scratch,
    )(
        frow, fcol, idr[:, None], idc[None, :], grow[:, None], gcol[None, :],
        lse_r[:, None], lse_c[None, :], cnt_r[:, None], cnt_c[None, :],
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _fused_loss(feats, ids, temperature, base_temperature, interpret, bm, bn):
    loss, _ = _fused_loss_fwd(
        feats, ids, temperature, base_temperature, interpret, bm, bn
    )
    return loss


def _fused_loss_fwd(feats, ids, temperature, base_temperature, interpret, bm, bn):
    n = feats.shape[0]
    gidx = jnp.arange(n, dtype=jnp.int32)
    loss_rows, lse, cnt = _fwd_call(
        feats, feats, ids, ids, gidx, gidx,
        temperature, base_temperature, interpret, bm, bn,
    )
    return jnp.mean(loss_rows), (feats, ids, lse[:, 0], cnt[:, 0])


def _fused_loss_bwd(temperature, base_temperature, interpret, bm, bn, res, g):
    feats, ids, lse, cnt = res
    n = feats.shape[0]
    gidx = jnp.arange(n, dtype=jnp.int32)
    coeff = (temperature / base_temperature) / n
    dfeats = _bwd_call(
        feats, feats, ids, ids, gidx, gidx, lse, lse, cnt, cnt,
        temperature, coeff, interpret, bm, bn,
    )
    return (g * dfeats, np.zeros(ids.shape, jax.dtypes.float0))


_fused_loss.defvjp(_fused_loss_fwd, _fused_loss_bwd)


# ---------------------------------------------------------------------------
# Sharded mode: the kernels inside shard_map over the data axis.
# ---------------------------------------------------------------------------


def _vma_of(x):
    """The varying-manual-axes set pallas_call outputs must carry, or None.

    Under ``shard_map(check_vma=False)`` (the supported mode for this kernel —
    the interpret-mode Pallas lowering cannot type kernel-internal constants)
    every array's vma is empty and pallas_call wants ``vma=None``.
    """
    try:
        return jax.typeof(x).vma or None
    except AttributeError:
        return None


def _vary(x, axis_name):
    """Mark a replicated array as device-varying for shard_map's vma typing.

    Idempotent: arrays already varying over ``axis_name`` (e.g. all_gather
    results, whose inputs were varying) pass through unchanged.
    """
    try:
        if axis_name in jax.typeof(x).vma:
            return x
    except AttributeError:
        pass
    return jax.lax.pcast(x, (axis_name,), to="varying")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _fused_sharded(
    feats_local, ids_global, axis_name,
    temperature, base_temperature, interpret, bm, bn,
):
    loss, _ = _fused_sharded_fwd(
        feats_local, ids_global, axis_name,
        temperature, base_temperature, interpret, bm, bn,
    )
    return loss


def _sharded_indices(feats_local, axis_name):
    m = feats_local.shape[0]
    p = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    grow = my * m + jnp.arange(m, dtype=jnp.int32)  # device-varying
    gcol = _vary(jnp.arange(m * p, dtype=jnp.int32), axis_name)
    return grow, gcol


def _fused_sharded_fwd(
    feats_local, ids_global, axis_name,
    temperature, base_temperature, interpret, bm, bn,
):
    all_feats = _vary(
        jax.lax.all_gather(feats_local, axis_name, tiled=True), axis_name
    )
    grow, gcol = _sharded_indices(feats_local, axis_name)
    ids_v = _vary(ids_global, axis_name)
    idr = jnp.take(ids_v, grow, axis=0)
    loss_rows, lse, cnt = _fwd_call(
        feats_local, all_feats, idr, ids_v, grow, gcol,
        temperature, base_temperature, interpret, bm, bn,
        vma=_vma_of(feats_local),
    )
    # mean over local anchor rows, pmean over the axis == the global mean.
    loss = jax.lax.pmean(jnp.mean(loss_rows), axis_name)
    return loss, (feats_local, ids_global, lse[:, 0], cnt[:, 0])


def _fused_sharded_bwd(
    axis_name, temperature, base_temperature, interpret, bm, bn, res, g
):
    feats_local, ids_global, lse, cnt = res
    if _vma_of(feats_local) is None:
        # check_vma=False: shard_map distributes a replicated output's
        # cotangent as per-shard 1/P shares — psum recovers the full scalar.
        g = jax.lax.psum(g, axis_name)
    m = feats_local.shape[0]
    p = jax.lax.axis_size(axis_name)
    n = m * p
    all_feats = _vary(
        jax.lax.all_gather(feats_local, axis_name, tiled=True), axis_name
    )
    # column-side softmax stats: O(N) vectors, the only cross-device residual.
    lse_all = _vary(jax.lax.all_gather(lse, axis_name, tiled=True), axis_name)
    cnt_all = _vary(jax.lax.all_gather(cnt, axis_name, tiled=True), axis_name)
    grow, gcol = _sharded_indices(feats_local, axis_name)
    ids_v = _vary(ids_global, axis_name)
    idr = jnp.take(ids_v, grow, axis=0)
    coeff = (temperature / base_temperature) / n
    dfeats = _bwd_call(
        feats_local, all_feats, idr, ids_v, grow, gcol,
        lse, lse_all, cnt, cnt_all,
        temperature, coeff, interpret, bm, bn,
        vma=_vma_of(feats_local),
    )
    return (g * dfeats, np.zeros(ids_global.shape, jax.dtypes.float0))


_fused_sharded.defvjp(_fused_sharded_fwd, _fused_sharded_bwd)


def supports(batch_size: int, n_views: int) -> bool:
    """True if the fused kernel can handle this [B, V, d] problem size."""
    n = batch_size * n_views
    return _pick_block(n, 256) is not None


def supports_sharded(batch_size: int, n_views: int, data_parallel: int) -> bool:
    """True if the sharded fused kernel fits this problem over P devices."""
    n = batch_size * n_views
    if data_parallel <= 0 or n % data_parallel:
        return False
    m = n // data_parallel
    return _pick_block(m, 256) is not None and _pick_block(n, 512) is not None


def fused_supcon_loss(
    features: jax.Array,
    labels: Optional[jax.Array] = None,
    *,
    temperature: float = 0.07,
    base_temperature: float = 0.07,
    interpret: bool = False,
    block_rows: int = 256,
    block_cols: int = 512,
) -> jax.Array:
    """Drop-in fused replacement for ``supcon_loss(..., contrast_mode='all')``.

    Args:
      features: ``[B, V, d]`` L2-normalized multi-view features (same contract
        as ``ops.losses.supcon_loss``).
      labels: optional ``[B]`` integer labels (SupCon); ``None`` = SimCLR.
      interpret: run the Pallas interpreter (CPU testing).
      block_rows / block_cols: VMEM tile caps; actual tiles are the largest
        divisors of ``V*B`` within the caps.

    Returns:
      Scalar loss, differentiable w.r.t. ``features``.
    """
    batch, n_views = features.shape[0], features.shape[1]
    n = batch * n_views
    feats = jnp.transpose(features, (1, 0, 2)).reshape(n, -1).astype(jnp.float32)
    if labels is None:
        sample_ids = jnp.tile(jnp.arange(batch, dtype=jnp.int32), n_views)
    else:
        sample_ids = jnp.tile(labels.astype(jnp.int32).reshape(-1), n_views)
    bm = _pick_block(n, block_rows)
    bn = _pick_block(n, block_cols)
    if bm is None or bn is None:
        raise ValueError(
            f"fused loss needs V*B divisible by 8, got {n}; use the dense path"
        )
    return _fused_loss(
        feats, sample_ids, float(temperature), float(base_temperature),
        bool(interpret), bm, bn,
    )


def fused_sharded_supcon_loss(
    feats_local: jax.Array,
    global_labels: Optional[jax.Array] = None,
    *,
    axis_name: str,
    temperature: float = 0.07,
    base_temperature: float = 0.07,
    n_views: int = 2,
    interpret: bool = False,
    block_rows: int = 256,
    block_cols: int = 512,
) -> jax.Array:
    """Fused SupCon/SimCLR loss over row-sharded features, inside shard_map.

    Same calling convention as ``parallel.collectives.ring_supcon_loss``:
    ``feats_local`` is this device's ``[m, D]`` contiguous block of the global
    view-major ``[V*B, D]`` L2-normalized feature matrix; ``global_labels`` is
    the REPLICATED ``[B]`` label vector for SupCon (``None`` = SimCLR).

    The contrast side is all-gathered (O(V·B·D), what the reference's NCCL
    gather moves anyway, ``main_supcon.py:268``); the fused kernels then keep
    every O(m·V·B) logits block in VMEM. Returns the replicated global scalar
    loss, differentiable w.r.t. ``feats_local`` — each device's backward
    computes the exact global gradient of its own rows (see module docstring).
    """
    m = feats_local.shape[0]
    p = jax.lax.axis_size(axis_name)
    n = m * p
    if n % n_views:
        raise ValueError(f"global rows {n} not divisible by n_views={n_views}")
    batch = n // n_views
    if global_labels is None:
        ids_global = jnp.tile(jnp.arange(batch, dtype=jnp.int32), n_views)
    else:
        ids_global = jnp.tile(
            global_labels.astype(jnp.int32).reshape(-1), n_views
        )
    bm = _pick_block(m, block_rows)
    bn = _pick_block(n, block_cols)
    if bm is None or bn is None:
        raise ValueError(
            f"sharded fused loss needs local rows {m} and global rows {n} "
            f"divisible by 8; use 'dense' or 'ring'"
        )
    return _fused_sharded(
        feats_local.astype(jnp.float32), ids_global, axis_name,
        float(temperature), float(base_temperature), bool(interpret), bm, bn,
    )
