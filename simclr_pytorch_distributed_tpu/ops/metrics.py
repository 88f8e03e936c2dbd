"""Metrics and meters.

``topk_accuracy`` matches the reference ``accuracy()`` (``util.py:37-51``): percent
of targets found in the top-k predictions, returned per requested k.
``AverageMeter`` mirrors ``util.py:19-34`` for host-side wall-clock/metric
averaging in the epoch drivers.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def topk_accuracy(
    output: jax.Array, target: jax.Array, topk: Sequence[int] = (1,)
) -> Tuple[jax.Array, ...]:
    """Top-k accuracy in percent, one scalar per k (reference ``util.py:37-51``)."""
    maxk = max(topk)
    batch_size = target.shape[0]
    # [maxk, batch] ranked predictions.
    _, pred = jax.lax.top_k(output, maxk)
    correct = pred.T == target[None, :]
    res = []
    for k in topk:
        correct_k = jnp.sum(correct[:k].astype(jnp.float32))
        res.append(correct_k * (100.0 / batch_size))
    return tuple(res)


def embedding_covariance(
    emb: jax.Array, center: bool = False, ddof: int = 0
) -> jax.Array:
    """``[D, D]`` (co)variance matrix of an ``[N, D]`` embedding batch.

    One covariance construction shared by the two consumers that must agree
    on it: the health diagnostics' effective-rank spectrum
    (train/supcon_step.contrastive_health_metrics — UNCENTERED second moment,
    ``center=False, ddof=0``, the PR-8 definition kept bitwise) and the
    VICReg covariance penalty (ops/losses.vicreg_loss — centered, unbiased:
    ``center=True, ddof=1``, the paper's estimator).
    """
    if center:
        emb = emb - jnp.mean(emb, axis=0, keepdims=True)
    return emb.T @ emb / (emb.shape[0] - ddof)


def topk_correct(logits: jax.Array, labels: jax.Array, ks=(1, 5)):
    """Per-batch top-k correct counts (sum-able across shards/batches).

    Shared by the probe/CE ring steps (train/linear.py, train/ce.py) and the
    pretrain step's online probe (train/supcon_step.py) — lives here rather
    than in train/linear.py so supcon_step can use it without an import
    cycle through the driver modules.
    """
    maxk = max(ks)
    _, pred = jax.lax.top_k(logits, maxk)
    hit = pred == labels[:, None]
    return {k: jnp.sum(jnp.any(hit[:, :k], axis=1)) for k in ks}


class AverageMeter:
    """Running value/average meter (reference ``util.py:19-34``)."""

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self.val = 0.0
        self.avg = 0.0
        self.sum = 0.0
        self.count = 0

    def update(self, val: float, n: int = 1) -> None:
        self.val = val
        self.sum += val * n
        self.count += n
        self.avg = self.sum / self.count


class MetricRing:
    """Device-side ``[window, K]`` fp32 metric ring + its host bookkeeping.

    The pre-ring ``MetricBuffer`` (deleted once the last trainer moved to the
    ring) batched the per-window readback into one ``device_get`` *call*, but
    each buffered step still held ~K live device scalars, so the runtime
    issued one tiny D2H descriptor per scalar — ~window*K transfers per flush
    (~110 ms/window, docs/PERF.md round 5). The ring
    closes that: the jitted step writes its
    metrics into row ``step % window`` of ONE device array
    (:meth:`write`, a ``dynamic_update_slice`` inside the compiled program,
    carried with the train state under the same donation discipline), and a
    flush is ONE contiguous D2H of that single small array
    (:meth:`resolve`). The host side records which ``(info, step)`` pairs are
    pending (:meth:`append` / :meth:`take_window`) and slices their rows out
    of the fetched block.

    ``device_get`` is injectable so tests can count transfers mechanically
    (``self.transfers`` counts flushes; each is exactly one call) or gate
    them on an event to prove dispatch/flush overlap.
    """

    def __init__(
        self,
        window: int,
        keys: Sequence[str],
        device_get: Optional[Callable] = None,
    ) -> None:
        if window <= 0:
            raise ValueError(f"ring window must be positive, got {window}")
        if len(set(keys)) != len(keys):
            raise ValueError(f"duplicate metric keys: {sorted(keys)}")
        self.window = int(window)
        # fixed column order shared by the trace-time writer and the host
        # reader — sorted so both sides derive it from the key SET alone
        self.keys = tuple(sorted(keys))
        self._device_get = device_get if device_get is not None else jax.device_get
        self._pending = []  # [(info, global_step)] appended, not yet flushed
        self.transfers = 0  # host transfers performed (== completed flushes)

    def init_buffer(self, sharding=None) -> jax.Array:
        """A fresh (zero) ring buffer; create one per epoch — the ring is
        transient driver state and is never checkpointed. ``sharding`` (the
        mesh's replicated sharding in the drivers) places the buffer where
        the jitted update expects it, so the first donation of each epoch
        doesn't relayout."""
        buf = jnp.zeros((self.window, len(self.keys)), jnp.float32)
        return buf if sharding is None else jax.device_put(buf, sharding)

    def write(self, ring: jax.Array, metrics: dict, step) -> jax.Array:
        """Trace-time: write ``metrics`` into row ``step % window``.

        Called INSIDE the jitted update with the traced ``state.step`` (the
        pre-increment global step), so the slot needs no extra carried
        counter and no host->device scalar per call.
        """
        if tuple(sorted(metrics)) != self.keys:
            raise ValueError(
                f"metric keys {sorted(metrics)} != ring keys {list(self.keys)}"
            )
        row = jnp.stack(
            [jnp.asarray(metrics[k]).astype(jnp.float32) for k in self.keys]
        )
        slot = jnp.asarray(step, jnp.int32) % self.window
        return jax.lax.dynamic_update_slice(
            ring, row[None, :], (slot, jnp.zeros((), jnp.int32))
        )

    def append(self, info, step: int) -> None:
        """Record that the step just dispatched wrote slot ``step % window``."""
        if len(self._pending) >= self.window:
            raise RuntimeError(
                f"metric ring overflow: {len(self._pending)} steps pending in "
                f"a window of {self.window} — flush at least every "
                f"{self.window} steps"
            )
        self._pending.append((info, int(step)))

    def pending_count(self) -> int:
        """Steps appended since the last flush (the current window's size)."""
        return len(self._pending)

    def take_window(self):
        """Hand the pending ``(info, step)`` list to a flush; clears it."""
        pending, self._pending = self._pending, []
        return pending

    def resolve(self, snapshot: jax.Array, pending):
        """ONE host transfer of the whole ring; returns ``[(info, {k: float})]``.

        ``snapshot`` must be a buffer later steps cannot donate away — the
        drivers hand a device-side copy taken at the window boundary.
        """
        if not pending:
            return []
        self.transfers += 1
        host = np.asarray(self._device_get(snapshot))
        out = []
        for info, step in pending:
            row = host[step % self.window]
            out.append(
                (info, {k: float(row[i]) for i, k in enumerate(self.keys)})
            )
        return out
