"""Cross-replica batch normalization, TPU-native.

The reference gets synchronized BN by swapping every BatchNorm2d for
``torch.nn.SyncBatchNorm`` (``main_supcon.py:223-224``), which all-reduces batch
statistics across GPUs with a dedicated CUDA kernel. On TPU under GSPMD there is
no kernel to swap: the train step is ONE logical program over the global batch,
so computing ``mean(x, axis=(0,1,2))`` on a batch-sharded NHWC array *is*
synchronized BN — XLA inserts the cross-chip reductions over ICI automatically.

This module therefore implements plain batch statistics plus:

- torch-matching semantics: biased variance for normalization, UNBIASED variance
  for the running-stat update, running update ``new = (1-m)*old + m*batch`` with
  ``momentum=0.1``, ``eps=1e-5`` (torch BatchNorm2d defaults used throughout the
  reference's ``networks/resnet_big.py``);
- an optional ``axis_name`` for explicit-collective contexts (``shard_map`` /
  ``pmap``), where stats are combined with ``lax.pmean`` — this is the
  per-device-program equivalent of SyncBatchNorm and also what a multi-host
  data-parallel step uses across the ``data`` axis;
- a grouped per-device mode (``sync=False, local_groups=G``) reproducing the
  reference's DEFAULT non-``--syncBN`` semantics (``main_supcon.py:223-224``
  converts to SyncBN only when the flag is given; otherwise each GPU's
  ``BatchNorm2d`` normalizes with its own local-batch statistics). Under GSPMD
  there are no per-device programs to scope the statistics to, so the batch is
  reshaped into G groups matching the per-device slices and statistics are
  computed per group. Running stats follow group 0 — DDP's default
  ``broadcast_buffers=True`` re-broadcasts rank 0's BN buffers at every
  forward, so rank 0's local statistics ARE the persistent ones upstream;
- fp32 statistics regardless of compute dtype (bf16 activations are normalized
  with fp32 mean/var, matching what mixed-precision SyncBN does).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn


def running_stats_update(
    ra_mean: jax.Array, ra_var: jax.Array,
    batch_mean: jax.Array, batch_var_biased: jax.Array,
    count: int, momentum: float,
) -> Tuple[jax.Array, jax.Array]:
    """The torch-convention running-stat update, single-sourced.

    ``new = (1-m)*old + m*batch`` with the BIASED batch variance rescaled
    to UNBIASED for the running buffer (torch BatchNorm semantics; module
    docstring). Shared by ``CrossReplicaBatchNorm`` and the fused Pallas
    conv path (``FusedTrainBN``), so the two impls cannot drift.
    """
    unbiased = batch_var_biased * (count / max(count - 1, 1))
    m = momentum
    return (
        (1.0 - m) * ra_mean + m * batch_mean,
        (1.0 - m) * ra_var + m * unbiased,
    )


class FusedTrainBN(nn.Module):
    """Parameter/variable shadow of ``CrossReplicaBatchNorm`` for a site
    whose normalization runs inside a kernel's forward or backward
    (``models.resnet.Bottleneck._tail_one_backward``, ops/pointwise_bwd.py).

    The caller computes the batch statistics and the normalization itself,
    so this module only owns what must live in the Flax tree: the affine
    params and the running-stat variables, under exactly the
    names/shapes/inits ``CrossReplicaBatchNorm`` creates — the param tree is
    the same whether or not the kernel engages, and a checkpoint restores
    either way.

    Call once with no statistics to fetch ``(scale, bias)``, then AGAIN with
    the batch moments to apply the running update
    (``running_stats_update``); train mode only — the eval path stays on
    the Flax module.
    """

    features: int
    momentum: float = 0.1

    @nn.compact
    def __call__(self, batch_mean=None, batch_var_biased=None, count: int = 0):
        scale = self.param(
            "scale", nn.initializers.ones, (self.features,), jnp.float32
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.features,), jnp.float32
        )
        ra_mean = self.variable(
            "batch_stats", "mean",
            lambda: jnp.zeros((self.features,), jnp.float32),
        )
        ra_var = self.variable(
            "batch_stats", "var",
            lambda: jnp.ones((self.features,), jnp.float32),
        )
        if batch_mean is not None and not self.is_initializing():
            ra_mean.value, ra_var.value = running_stats_update(
                ra_mean.value, ra_var.value, batch_mean, batch_var_biased,
                count, self.momentum,
            )
        return scale, bias


class CrossReplicaBatchNorm(nn.Module):
    """BatchNorm over the (logically global) batch for NHWC activations.

    Attributes:
      momentum: torch-convention running-stat momentum (weight of the NEW batch
        statistic; torch default 0.1).
      epsilon: numerical-stability constant (torch default 1e-5).
      use_running_average: eval mode — normalize with running stats.
      axis_name: if set, batch statistics are additionally ``lax.pmean``-ed over
        this mapped axis (shard_map/pmap path). Leave ``None`` under GSPMD jit,
        where sharded-batch statistics are already global.
      sync: if False, skip the ``axis_name`` reduction even when provided —
        reproduces the reference's non-``--syncBN`` per-device BN semantics.
      local_groups: per-device BN under GSPMD jit (``axis_name=None``): when
        ``sync=False`` and ``local_groups=G > 1``, the batch is split into G
        groups (the data-parallel device slices) and each group normalizes
        with its OWN statistics — the reference's default per-GPU BN.
      group_views: view-major folds in the leading axis. The train step flattens
        the two crops view-major (``[v1 rows | v2 rows]``, supcon_step.py), while
        the reference's per-GPU batch holds BOTH views of its image slice —
        ``group_views=2`` makes group g = {view-1 slice g} ∪ {view-2 slice g},
        matching that composition exactly.
    """

    momentum: float = 0.1
    epsilon: float = 1e-5
    use_running_average: bool = False
    axis_name: Optional[str] = None
    sync: bool = True
    local_groups: int = 1
    group_views: int = 1
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array, use_running_average: Optional[bool] = None):
        use_ra = nn.merge_param(
            "use_running_average", self.use_running_average, use_running_average
        )
        num_features = x.shape[-1]
        reduce_axes = tuple(range(x.ndim - 1))  # (N, H, W) for NHWC

        scale = self.param("scale", nn.initializers.ones, (num_features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (num_features,), jnp.float32)

        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((num_features,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((num_features,), jnp.float32)
        )

        xf = x.astype(jnp.float32)
        grouped = (
            not use_ra
            and not self.sync
            and self.axis_name is None
            and self.local_groups > 1
            # init traces with a tiny example batch (e.g. 2 rows) that need
            # not divide into the groups; shapes/params don't depend on the
            # statistics path, so init uses the whole-batch branch
            and not self.is_initializing()
        )
        if grouped:
            # Per-device BN under one GSPMD program: statistics scoped to the
            # G data-parallel slices instead of the global batch. The [G, C]
            # stats may straddle shard boundaries — XLA inserts tiny
            # reductions; semantics (the reference's default per-GPU BN, not
            # perf) is the point of this mode.
            v, g = self.group_views, self.local_groups
            n = x.shape[0]
            if n % (v * g):
                raise ValueError(
                    f"batch {n} not divisible into {v} views x {g} BN groups"
                )
            spatial = 1
            for a in range(1, x.ndim - 1):
                spatial *= x.shape[a]
            count = (n // g) * spatial
            xg = xf.reshape((v, g, n // (v * g)) + x.shape[1:])
            red = (0,) + tuple(range(2, xg.ndim - 1))
            mean = jnp.mean(xg, axis=red)  # [G, C]
            mean_sq = jnp.mean(jnp.square(xg), axis=red)
            var = mean_sq - jnp.square(mean)  # biased, per group
            if not self.is_initializing():
                # Running stats track group 0: DDP's broadcast_buffers=True
                # re-broadcasts rank 0's BN buffers every forward, so rank 0's
                # local statistics are the persistent ones in the reference.
                ra_mean.value, ra_var.value = running_stats_update(
                    ra_mean.value, ra_var.value, mean[0], var[0],
                    count, self.momentum,
                )
            bshape = (1, g) + (1,) * (xg.ndim - 3) + (num_features,)
            yg = (xg - mean.reshape(bshape)) * jax.lax.rsqrt(
                var.reshape(bshape) + self.epsilon
            )
            y = yg.reshape(x.shape) * scale + bias
            return y.astype(self.dtype or x.dtype)
        if use_ra:
            mean, var = ra_mean.value, ra_var.value
        else:
            mean = jnp.mean(xf, axis=reduce_axes)
            mean_sq = jnp.mean(jnp.square(xf), axis=reduce_axes)
            count = 1
            for a in reduce_axes:
                count *= x.shape[a]
            if self.axis_name is not None and self.sync:
                mean = jax.lax.pmean(mean, self.axis_name)
                mean_sq = jax.lax.pmean(mean_sq, self.axis_name)
                count *= jax.lax.axis_size(self.axis_name)
            var = mean_sq - jnp.square(mean)  # biased — used for normalization

            if not self.is_initializing():
                # torch running update: biased mean, UNBIASED variance.
                ra_mean.value, ra_var.value = running_stats_update(
                    ra_mean.value, ra_var.value, mean, var,
                    count, self.momentum,
                )

        y = (xf - mean) * jax.lax.rsqrt(var + self.epsilon) * scale + bias
        return y.astype(self.dtype or x.dtype)
