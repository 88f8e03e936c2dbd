"""Explicit-collective building blocks: ring-sharded contrastive loss.

The reference materializes the full [2B, 2B] NT-Xent logits matrix on every GPU
(``losses.py:64-66``) after all-gathering every rank's features
(``main_supcon.py:268-269``). That is fine at B=256 but quadratic in HBM: at the
ImageNet-scale bs=4096 recipe the matrix is 8192x8192 per device, and the full
feature gather costs O(2B·D) replicated memory.

``ring_supcon_loss`` is the ring-attention-style decomposition (SURVEY.md §5
long-context row): anchors stay sharded; contrast feature blocks rotate around
the ``data`` ring with ``lax.ppermute`` while each device streams a numerically
exact online log-sum-exp (flash-softmax style) and accumulates positive-pair
similarities. Per-device memory drops to O((2B/P)^2) per ring step and the
block matmuls overlap with neighbor transfers over ICI.

Exactness: the reference's detached row-max subtraction (``losses.py:68-69``)
cancels in ``logit - logsumexp``, so the streamed loss equals the dense loss to
fp tolerance — verified against ``ops.losses.supcon_loss`` in
``tests/test_ring_loss.py``. Differentiable end-to-end (scan + ppermute).

Layout convention matches the train step: global rows are view-major
``[v1 of all samples; v2 of all samples]`` (``main_supcon.py:279``), sharded
contiguously: device d owns rows ``[d*m, (d+1)*m)``, m = 2B/P.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def ring_supcon_loss(
    feats_local: jax.Array,
    global_labels: Optional[jax.Array] = None,
    *,
    axis_name: str,
    temperature: float = 0.07,
    base_temperature: float = 0.07,
    n_views: int = 2,
) -> jax.Array:
    """SupCon/SimCLR loss over row-sharded L2-normalized features.

    Args:
      feats_local: ``[m, D]`` this device's block of the global view-major
        feature matrix ``[V*B, D]`` (already normalized).
      global_labels: ``[B]`` REPLICATED labels for SupCon, or ``None`` for
        SimCLR (positives = other views of the same sample).
      axis_name: mesh axis the rows are sharded over.
      temperature / base_temperature: as in ``ops.losses.supcon_loss``.
      n_views: V (2 for the TwoCrop recipe).

    Returns:
      Per-device mean anchor loss pmean-ed over the axis == the global loss.
    """
    m, _ = feats_local.shape
    p = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    rows_total = m * p  # V*B
    batch = rows_total // n_views

    g_anchor = my * m + jnp.arange(m)  # global row ids of local anchors
    anchor_sample = g_anchor % batch

    if global_labels is not None:
        anchor_label = global_labels[anchor_sample]

    perm = [(i, (i + 1) % p) for i in range(p)]

    def ring_step(carry, step):
        block, run_max, run_sum, pos_acc, pos_cnt = carry
        src = (my - step) % p  # who this block belongs to
        g_col = src * m + jnp.arange(m)
        sims = (feats_local @ block.T) / temperature  # [m, m] MXU tile

        self_mask = g_anchor[:, None] == g_col[None, :]
        sims_no_self = jnp.where(self_mask, _NEG_INF, sims)

        # online log-sum-exp over non-self columns
        blk_max = jnp.max(sims_no_self, axis=1)
        new_max = jnp.maximum(run_max, blk_max)
        run_sum = run_sum * jnp.exp(run_max - new_max) + jnp.sum(
            jnp.exp(sims_no_self - new_max[:, None]), axis=1
        )

        # positive pairs (excluding self): same sample (SimCLR) / same label (SupCon)
        col_sample = g_col % batch
        if global_labels is None:
            pos_mask = (anchor_sample[:, None] == col_sample[None, :]) & ~self_mask
        else:
            col_label = global_labels[col_sample]
            pos_mask = (anchor_label[:, None] == col_label[None, :]) & ~self_mask
        pos_acc = pos_acc + jnp.sum(jnp.where(pos_mask, sims, 0.0), axis=1)
        pos_cnt = pos_cnt + jnp.sum(pos_mask, axis=1)

        block = jax.lax.ppermute(block, axis_name, perm)
        return (block, new_max, run_sum, pos_acc, pos_cnt), None

    def dev_varying(x):
        # mark fresh accumulators as device-varying for shard_map's vma
        # typing
        return jax.lax.pcast(x, (axis_name,), to="varying")

    init = (
        feats_local,
        dev_varying(jnp.full((m,), _NEG_INF, feats_local.dtype)),
        dev_varying(jnp.zeros((m,), feats_local.dtype)),
        dev_varying(jnp.zeros((m,), feats_local.dtype)),
        dev_varying(jnp.zeros((m,), feats_local.dtype)),
    )
    (_, run_max, run_sum, pos_acc, pos_cnt), _ = jax.lax.scan(
        ring_step, init, jnp.arange(p)
    )

    log_denom = run_max + jnp.log(run_sum)
    mean_log_prob_pos = pos_acc / pos_cnt - log_denom
    loss_local = -(temperature / base_temperature) * mean_log_prob_pos
    return jax.lax.pmean(jnp.mean(loss_local), axis_name)


def gather_global_labels(labels_local: jax.Array, axis_name: str) -> jax.Array:
    """All-gather the (tiny) per-device label shards into the replicated [B]
    vector the ring loss consumes — the fix for the reference's distributed
    SupCon crash (local labels vs gathered features, main_supcon.py:287-288)."""
    return jax.lax.all_gather(labels_local, axis_name).reshape(-1)
