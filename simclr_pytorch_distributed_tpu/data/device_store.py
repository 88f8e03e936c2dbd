"""Device-resident data placement: the HBM-resident epoch buffer.

``docs/PERF.md`` round-5 measured the last unfixed gap between the production
driver loop and the pure compiled step: the per-step uint8 H2D transfer
(``shard_host_batch`` -> ``device_put``) cost a volatile 0-10 ms/step on the
round-5 machine, while a device-resident batch sat at a stable 64.6-65.2
ms/step floor. For datasets that
fit an HBM budget (CIFAR-10/100 train is ~150 MB uint8), this module removes
the per-step transfer entirely:

- the full uint8 dataset is uploaded ONCE at startup, replicated per device
  (replication is what keeps the per-epoch shuffle gather collective-free:
  every device gathers its own rows from its own full copy; the cost is
  bounded and pre-checked against the budget);
- per epoch the host computes the SAME numpy permutation ``EpochLoader``
  already uses (``data/pipeline.py`` ``_epoch_order`` — this class holds the
  loader and calls it, so there is exactly one permutation source) and ships
  only the int32 index matrix (~200 KB for CIFAR: ONE transfer per epoch,
  asserted mechanically via the injectable ``index_put`` hook);
- one compiled program gathers the permuted epoch into a ``[steps, batch,
  ...]`` buffer sharded batch-wise over the mesh's ``data`` axis (each
  process's devices hold only that process's slice of every global batch —
  the multi-host layout of ``EpochLoader``'s per-process slicing); the
  per-epoch gather is the ONLY row gather, so the TPU gather-lowering trap
  (the 227x crop lesson, docs/PERF.md) never applies per-step;
- each train step slices its batch with a contiguous leading-axis
  ``lax.dynamic_slice`` at ``state.step % steps_per_epoch``
  (:func:`slice_epoch_step`; the buffer is a NON-donated jit argument), so
  the hot loop is dispatch-only: no host work, no transfer, no sync.

Batch composition is bit-identical to the host loader by construction (same
permutation, same drop_last truncation, same per-process slicing), so
accuracy ratchets carry over; mid-epoch resume is a slice-offset shift
(``state.step`` restores from the checkpoint and the in-program position
follows). Proven byte-for-byte by ``tests/test_device_store.py``.

Full residency is a small-dataset (CIFAR-geometry) luxury: the real SimCLR
regime is 224x224 ImageNet-scale data that will never fit an HBM budget.
:class:`WindowStore` generalizes the same dispatch-only hot loop to datasets
that don't fit: the device trains from a resident window of
epoch-permutation-ordered batches while a host prefetch thread stages the
NEXT window into the shadow buffer, so the loop pays ONE H2D per window
instead of one per step — and the permutation source is still the driver's
own ``EpochLoader``, so the bit-identity contract (and its proof
obligations: full epochs, mid-epoch resume, multi-process slicing) carries
over unchanged. Proven by ``tests/test_window_store.py``.

``resolve_data_placement`` implements the ``--data_placement`` contract as a
three-way ladder: fully resident (``device``) when the dataset fits the
budget, windowed (``window``) when ``2 x window_bytes`` fits — memmap-backed
``data/folder.py`` trees are *windowable* (each window's host gather reads
only that window's rows), not host-degraded — and ``host`` only as the true
fallback (one startup banner naming the reason); it never OOMs, and the
verdict is collective across processes because placement selects which
collective programs a process runs.
"""

from __future__ import annotations

import logging
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from simclr_pytorch_distributed_tpu.parallel.mesh import (
    DATA_AXIS,
    epoch_buffer_sharding,
    replicated_sharding,
)
from simclr_pytorch_distributed_tpu.utils import tracing

logger = logging.getLogger(__name__)

# Budget used when the backend reports no memory stats (CPU, some drivers):
# conservative vs any real accelerator HBM, far above CIFAR-scale data.
DEFAULT_BUDGET_BYTES = 4 << 30
# Fraction of the reported free per-device memory the store may claim — the
# model, optimizer state, activations, and the XLA allocator's slack own the
# rest. Deliberately conservative: 'auto' must degrade, never OOM.
BUDGET_FRACTION = 0.4
# Batches per resident window when --data_window_batches is not given: large
# enough that the per-window upload amortizes to noise (the A/B expectation
# removes delay * (1 - 1/W) of a per-step penalty), small enough that
# 2x window bytes stays far under any real HBM budget at 224x224 geometry.
DEFAULT_WINDOW_BATCHES = 32


def budget_override_bytes(mb) -> Optional[int]:
    """``--device_budget_mb`` -> a ``resolve_data_placement`` budget override
    in bytes; 0/None (the flag default) keeps the computed budget."""
    return int(mb) << 20 if mb else None


def dataset_nbytes(images: np.ndarray, labels: np.ndarray) -> int:
    return int(images.nbytes) + int(np.asarray(labels).nbytes)


def _is_memmap_backed(arr) -> bool:
    """True if ``arr`` is an ``np.memmap`` or a view over one.

    Wrappers strip the subclass without copying: ``np.ascontiguousarray`` on
    a C-contiguous memmap (``EpochLoader.__init__``) returns a plain
    ``ndarray`` VIEW whose ``base`` chain still ends at the on-disk file —
    a bare ``isinstance`` check would wave it through and residency would
    silently page the whole tree into RAM/HBM.
    """
    while arr is not None:
        if isinstance(arr, np.memmap):
            return True
        arr = getattr(arr, "base", None)
    return False


def device_budget_bytes(fraction: float = BUDGET_FRACTION) -> int:
    """Per-device placement budget: ``fraction`` of free device memory.

    The CPU backend has no ``memory_stats()`` by nature and gets a fixed
    conservative default. On a TPU the stats are the device's own account
    of its memory: a missing ``bytes_limit`` there raises rather than
    placing data against a guess.
    """
    device = jax.local_devices()[0]
    if device.platform == "cpu":
        return DEFAULT_BUDGET_BYTES
    stats = device.memory_stats() or {}
    if not stats.get("bytes_limit"):
        raise RuntimeError(
            f"{device} reports no memory_stats()['bytes_limit']: cannot "
            "size the device-resident data budget"
        )
    free = int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
    return max(0, int(free * fraction))


def resident_bytes_per_device(
    images: np.ndarray, labels: np.ndarray, global_batch_size: int,
    data_parallel: int,
) -> int:
    """Per-device HBM the store will claim: the replicated dataset plus the
    double-buffered epoch buffer shard.

    The epoch buffer holds the drop_last-truncated epoch
    (``steps * global_batch`` rows) sharded ``data_parallel`` ways; 2x
    covers the transient overlap while epoch e+1's gather output coexists
    with epoch e's buffer (and matches the ISSUE's stated bound).
    """
    n = len(images)
    used_rows = (n // global_batch_size) * global_batch_size
    row_bytes = (
        int(images.nbytes // max(1, n))
        + int(np.asarray(labels).nbytes // max(1, n))
    )
    buffer_shard = -(-used_rows * row_bytes // max(1, data_parallel))  # ceil
    return dataset_nbytes(images, labels) + 2 * buffer_shard


def windowed_bytes_per_device(
    images: np.ndarray, labels: np.ndarray, global_batch_size: int,
    data_parallel: int, window_batches: int,
) -> int:
    """Per-device HBM the WINDOW store will claim: 2x one window shard
    (the resident window the device trains from plus the shadow buffer the
    prefetch thread stages the next window into). Unlike residency, the
    dataset itself never lands on device, so this bound is independent of
    dataset size — the whole point of the ladder's middle rung.
    """
    n = len(images)
    row_bytes = (
        int(images.nbytes // max(1, n))
        + int(np.asarray(labels).nbytes // max(1, n))
    )
    steps = max(1, n // global_batch_size)
    w = min(max(1, window_batches), steps)  # the store clamps identically
    shard = -(-w * global_batch_size * row_bytes // max(1, data_parallel))
    return 2 * shard


def _agree_across_processes(local_ok: bool) -> bool:
    """Collective AND of the per-process placement verdicts.

    The budget reads LOCAL ``memory_stats``, which can differ across hosts
    (fragmentation, co-resident allocations) — but placement selects which
    COLLECTIVE programs a process runs (the sharded per-epoch gather vs
    window uploads vs per-step puts), so a split verdict would deadlock
    the pod at the first epoch. The invariant is that the CALL COUNT is
    identical on every process during one resolution (the
    ``requested_global`` pattern, utils/preempt.py): explicit placements
    call it once, the 'auto' ladder once per rung it walks — which
    matches because each rung's allgathered outcome is identical
    everywhere, so all processes decide together whether the next rung's
    collective runs. All act on the AND: one over-budget host sends the
    whole job down the ladder. Single process short-circuits — no
    collective in the common case.
    """
    if jax.process_count() == 1:
        tracing.clock_anchor("placement")
        return local_ok
    from jax.experimental import multihost_utils

    # a split placement verdict is the canonical silent-deadlock seed — the
    # flight recorder keeps each host's local vote and the agreed outcome so
    # a wedged pod's dumps show who voted what at which rung
    with tracing.span(
        "placement_decision", track="main:collective", local=bool(local_ok)
    ):
        flags = multihost_utils.process_allgather(
            np.asarray([local_ok], np.int32)
        )
    # the startup alignment ruler: every process just left the same
    # collective, so this stamp is the same physical instant on each
    # host's clock (trace_report --fleet; identical call count per
    # resolution is this function's documented invariant)
    tracing.clock_anchor("placement")
    return bool(np.asarray(flags).all())


def resolve_data_placement(
    placement: str,
    images: np.ndarray,
    labels: np.ndarray,
    global_batch_size: int,
    mesh,
    budget_bytes: Optional[int] = None,
    window_batches: Optional[int] = None,
) -> str:
    """The ``--data_placement`` decision, logged. Returns 'host', 'device',
    or 'window'.

    - ``host``: always honored (the pre-existing per-step H2D loop).
    - ``device``/``window``: honored or a loud ``ValueError`` at startup —
      an explicit request that cannot be satisfied must fail before the
      first step, not OOM mid-run or silently degrade. On a multi-host job
      ANY process's rejection raises on EVERY process (collective verdict):
      one host erroring out while its peers build the store would strand
      the peers in the store's collectives.
    - ``auto``: the three-way ladder, each rung a collective verdict —
      'device' when the dataset is a plain in-RAM array within the budget
      ON EVERY PROCESS, else 'window' when the double-buffered window
      (``2 x window_bytes``; memmap-backed datasets qualify — each window's
      host gather reads only that window's rows) fits everywhere, else
      'host' with a one-line startup banner naming the reason.
    """
    if placement == "host":
        return "host"
    if placement not in ("device", "window", "auto"):
        raise ValueError(f"unknown data_placement {placement!r}")

    def reject(reason: str) -> str:
        if placement != "auto":
            raise ValueError(
                f"--data_placement {placement} cannot be satisfied: {reason}"
                f" — use 'auto' (walks the device->window->host ladder with "
                f"a banner) or 'host'"
            )
        logger.warning("data_placement auto -> host: %s", reason)
        return "host"

    data_parallel = mesh.shape.get(DATA_AXIS, 1)
    budget = device_budget_bytes() if budget_bytes is None else budget_bytes
    w = window_batches or DEFAULT_WINDOW_BATCHES

    # rung 1: full residency (the dataset itself on device)
    if _is_memmap_backed(images) or _is_memmap_backed(labels):
        resident_reason = (
            "dataset is memmap-backed (data/folder.py on-disk cache); "
            "device residency would page the whole tree into RAM/HBM"
        )
        need = None
    else:
        need = resident_bytes_per_device(
            images, labels, global_batch_size, data_parallel
        )
        resident_reason = None if need <= budget else (
            f"dataset needs {need / 1e6:.1f} MB/device (replicated data + "
            f"2x epoch-buffer shard) > budget {budget / 1e6:.1f} MB"
        )
    # rung 2: the double-buffered window (dataset stays on host)
    window_need = windowed_bytes_per_device(
        images, labels, global_batch_size, data_parallel, w
    )
    window_reason = None if window_need <= budget else (
        f"double-buffered {w}-batch window needs {window_need / 1e6:.1f} "
        f"MB/device > budget {budget / 1e6:.1f} MB"
    )

    def log_device() -> str:
        logger.info(
            "data_placement: device (%.1f MB/device resident: %.1f MB "
            "dataset + double-buffered epoch shard; budget %.1f MB)",
            need / 1e6, dataset_nbytes(images, labels) / 1e6, budget / 1e6,
        )
        return "device"

    def log_window(why_not_resident: str) -> str:
        logger.info(
            "data_placement: window (%d batches/window, %.1f MB/device "
            "double-buffered; budget %.1f MB; not fully resident: %s)",
            w, window_need / 1e6, budget / 1e6, why_not_resident,
        )
        return "window"

    peer = (
        "a peer process rejected {0} placement (per-host free-memory "
        "budgets differ); placement selects collective programs, so it "
        "must agree across hosts"
    )
    if placement == "device":
        # every process reaches this exact point once, whatever its local
        # verdict — the allgather schedules must match
        ok_everywhere = _agree_across_processes(resident_reason is None)
        if resident_reason is not None:
            return reject(resident_reason)
        if not ok_everywhere:
            return reject(peer.format("device"))
        return log_device()
    if placement == "window":
        ok_everywhere = _agree_across_processes(window_reason is None)
        if window_reason is not None:
            return reject(window_reason)
        if not ok_everywhere:
            return reject(peer.format("window"))
        return log_window(resident_reason or "explicit window request")
    # auto: walk the ladder. Each rung is one matched collective point; the
    # rung-1 result is identical on every process, so all processes agree
    # on whether rung 2's collective runs at all.
    if _agree_across_processes(resident_reason is None):
        return log_device()
    if _agree_across_processes(window_reason is None):
        return log_window(resident_reason or peer.format("device"))
    return reject(window_reason or peer.format("window"))


def make_store(
    placement: str,
    loader,
    mesh,
    budget_bytes: Optional[int] = None,
    window_batches: Optional[int] = None,
):
    """The drivers' one-call entry point: resolve ``--data_placement``
    against the LOADER'S OWN arrays and geometry, build the matching store
    — :class:`DeviceStore` ('device'), :class:`WindowStore` ('window') —
    or return ``None`` (the host loop).

    Resolving from ``loader.images``/``loader.labels`` (not the raw
    ``load_dataset`` arrays) matters: the loader may have copied a
    non-contiguous input via ``ascontiguousarray``, and what resolution
    inspects must be exactly what the store would upload — two sources
    could drift on the memmap check.

    All of it is the set-up span ``store`` (track ``setup``): placement and
    the dataset's upload.
    """
    with tracing.span("store", track=tracing.SETUP_TRACK):
        placement = resolve_data_placement(
            placement, loader.images, loader.labels, loader.global_batch_size,
            mesh, budget_bytes=budget_bytes, window_batches=window_batches,
        )
        if placement == "device":
            return DeviceStore(loader, mesh)
        if placement == "window":
            return WindowStore(
                loader, mesh, window_batches or DEFAULT_WINDOW_BATCHES
            )
        return None


def _validate_loader_geometry(loader, mesh, kind: str) -> None:
    """The shared store-construction contract (DeviceStore and WindowStore
    alike): a drop_last loader whose global batch shards evenly over the
    mesh's data axis."""
    if not loader.drop_last:
        raise ValueError(
            f"{kind} requires drop_last loaders (the training path);"
            " ragged tails have no static step shape"
        )
    data_parallel = mesh.shape.get(DATA_AXIS, 1)
    if loader.global_batch_size % data_parallel != 0:
        raise ValueError(
            f"global batch {loader.global_batch_size} not divisible by "
            f"the mesh's {data_parallel}-way data axis"
        )


def epoch_index_matrix(loader, epoch: int) -> np.ndarray:
    """The epoch's global batch composition as a ``[steps, batch]`` int32
    matrix — EXACTLY ``EpochLoader``'s permutation, drop_last-truncated and
    reshaped. Row ``s`` column range ``[p*per_proc, (p+1)*per_proc)`` is
    process ``p``'s slice of step ``s``'s global batch (pipeline.py
    ``_batches``), which is why sharding the matrix column-wise over the
    'data' axis reproduces the multi-host layout."""
    order = loader._epoch_order(epoch)
    steps, batch = loader.steps_per_epoch, loader.global_batch_size
    return np.ascontiguousarray(
        order[: steps * batch].reshape(steps, batch).astype(np.int32)
    )


def slice_epoch_step(epoch_images, epoch_labels, position):
    """One step's batch out of the resident ``[steps, batch, ...]`` buffers:
    a contiguous leading-axis dynamic slice (each device slices its own
    batch shard locally — no communication, no gather)."""
    images = jax.lax.dynamic_index_in_dim(
        epoch_images, position, axis=0, keepdims=False
    )
    labels = jax.lax.dynamic_index_in_dim(
        epoch_labels, position, axis=0, keepdims=False
    )
    return images, labels


class DeviceStore:
    """HBM-resident dataset + per-epoch shuffled buffer for one loader.

    Wraps the driver's ``EpochLoader`` — the store never computes its own
    permutation or geometry, so host and device placement cannot drift.

    ``index_put`` is the injectable per-epoch index upload (tests assert the
    one-transfer-per-epoch contract through it, the MetricRing pattern).
    """

    # the in-program slice axis is the whole epoch (drivers pass this to the
    # update builders; WindowStore overrides with its window length)
    window_batches: Optional[int] = None

    def __init__(
        self,
        loader,
        mesh,
        *,
        index_put: Optional[Callable[[np.ndarray], jax.Array]] = None,
    ):
        _validate_loader_geometry(loader, mesh, "DeviceStore")
        self.loader = loader
        self.mesh = mesh
        self.steps_per_epoch = loader.steps_per_epoch
        self.global_batch_size = loader.global_batch_size

        repl = replicated_sharding(mesh)
        img_ndim = loader.images.ndim
        # same [S, B] layout as the labels epoch buffer — the index columns
        # must stay aligned with the buffer slices they produce
        self._idx_sharding = epoch_buffer_sharding(mesh, 2)
        self._index_put = index_put or (
            lambda idx: jax.make_array_from_callback(
                idx.shape, self._idx_sharding, lambda i: idx[i]
            )
        )
        # the one-time upload: full dataset replicated per device (each
        # process feeds its own local devices from its own in-RAM copy)
        labels32 = np.ascontiguousarray(np.asarray(loader.labels, np.int32))
        images = np.ascontiguousarray(loader.images)
        self.images = jax.make_array_from_callback(
            images.shape, repl, lambda i: images[i]
        )
        self.labels = jax.make_array_from_callback(
            labels32.shape, repl, lambda i: labels32[i]
        )

        def gather(ds_images, ds_labels, idx):
            # [S, B] indices into the replicated [N, ...] dataset -> the
            # shuffled [S, B, ...] epoch buffer; indices are host-validated
            # by construction (a permutation of range(N))
            return (
                jnp.take(ds_images, idx, axis=0, mode="clip"),
                jnp.take(ds_labels, idx, axis=0, mode="clip"),
            )

        self._gather = jax.jit(
            gather,
            in_shardings=(repl, repl, self._idx_sharding),
            out_shardings=(
                epoch_buffer_sharding(mesh, img_ndim + 1),
                epoch_buffer_sharding(mesh, 2),
            ),
        )
        self._cached_epoch: Optional[int] = None
        self._buffers: Optional[Tuple[jax.Array, jax.Array]] = None

    def epoch_buffers(self, epoch: int) -> Tuple[jax.Array, jax.Array]:
        """The epoch's shuffled resident ``(images[S,B,H,W,C], labels[S,B])``.

        One int32 index upload + one compiled gather per epoch; repeated
        calls for the same epoch return the cached buffers. The previous
        epoch's buffers are dropped as the new ones land (the 2x
        double-buffer bound in :func:`resident_bytes_per_device`).
        """
        if self._cached_epoch != epoch:
            # host-visible boundary (the ONE per-epoch upload + gather
            # dispatch); the span records dispatch-side time only — no sync
            with tracing.span("epoch_gather", track="main:data", epoch=epoch):
                idx = self._index_put(epoch_index_matrix(self.loader, epoch))
                self._buffers = self._gather(self.images, self.labels, idx)
            self._cached_epoch = epoch
        return self._buffers

    def batch_buffers(self, epoch: int, idx: int) -> Tuple[jax.Array, jax.Array]:
        """The store API the driver loops consume (shared with
        :class:`WindowStore`): the device buffers step ``idx`` of ``epoch``
        slices its batch from. Here that is the whole cached epoch buffer —
        the per-step position is derived on device from ``state.step``."""
        del idx  # every step of the epoch reads the same resident buffers
        return self.epoch_buffers(epoch)

    def close(self) -> None:
        """Release driver-owned resources (shared API with WindowStore);
        the resident store holds no threads — nothing to do."""


class WindowStore:
    """Double-buffered streaming window: the dispatch-only hot loop for
    datasets that don't fit in HBM.

    The device trains from a resident ``[window_batches, batch, ...]``
    window of epoch-permutation-ordered batches while the host prefetch
    thread stages the NEXT window into the shadow buffer, so the hot loop
    pays ONE H2D per window instead of one per step — and between window
    boundaries it is exactly PR 5's dispatch-only loop (no host work, no
    transfer, no sync). The swap at a boundary is a handle exchange: the
    prefetched upload was dispatched asynchronously while the previous
    window trained, so the caller never blocks on a landed transfer.

    One permutation source: window ``w`` of epoch ``e`` is rows
    ``[w*W, (w+1)*W)`` of :func:`epoch_index_matrix` — EXACTLY the driver's
    ``EpochLoader`` permutation, drop_last-truncated, with process ``p``'s
    column block of every row being that process's loader slice (the same
    multi-host layout as the resident store, ``epoch_buffer_sharding``).
    The short last window of an epoch is padded back to ``W`` batches with
    rows the step never slices (the in-program position
    ``epoch_position(step) % W`` stays below the tail length), so every
    window shares ONE compiled step program. Mid-epoch resume is a window +
    slice offset shift: the driver asks for ``batch_buffers(epoch,
    start_step)``, which lands in window ``start_step // W``, and the
    restored ``state.step`` positions the in-window slice.

    The host gather for one window reads only that window's rows — and on
    a pod, only THIS process's column block of them (``_stage``) — so on a
    memmap-backed dataset (``data/folder.py``) the epoch streams through
    the page cache window by window instead of paging the whole tree into
    RAM, which is why the placement ladder marks memmap trees *windowable*
    rather than host-degraded.

    ``window_put`` is the injectable per-window upload, receiving the
    process-local ``[W, B/process_count, ...]`` blocks (tests assert the
    one-upload-per-window, window-sized transfer contract through it — the
    ``index_put`` pattern). ``prefetch=False`` stages every window in the
    caller's thread: deterministic upload ordering for tests and for the
    serialized-link A/B proxy (``scripts/window_ab.py``), where overlap
    would hide the modeled transfer.
    """

    def __init__(
        self,
        loader,
        mesh,
        window_batches: int = DEFAULT_WINDOW_BATCHES,
        *,
        window_put: Optional[Callable] = None,
        prefetch: bool = True,
    ):
        _validate_loader_geometry(loader, mesh, "WindowStore")
        if window_batches < 1:
            raise ValueError(
                f"window_batches must be >= 1, got {window_batches}"
            )
        self.loader = loader
        self.mesh = mesh
        self.steps_per_epoch = loader.steps_per_epoch
        self.global_batch_size = loader.global_batch_size
        self.window_batches = min(window_batches, loader.steps_per_epoch)
        self.n_windows = -(-loader.steps_per_epoch // self.window_batches)
        self._img_sharding = epoch_buffer_sharding(mesh, loader.images.ndim + 1)
        self._lab_sharding = epoch_buffer_sharding(mesh, 2)
        self._window_put = window_put or self._default_put
        self._executor = (
            ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="WindowStore-prefetch"
            )
            if prefetch else None
        )
        self._epoch_idx: Optional[Tuple[int, np.ndarray]] = None
        self._current = None  # (epoch, window, (images, labels))
        self._next = None  # (epoch, window, Future)

    def _default_put(self, images: np.ndarray, labels: np.ndarray):
        """Async H2D of one PROCESS-LOCAL window block under the
        epoch-buffer layout (the ``shard_host_batch`` convention: plain
        ``device_put`` single-process, global-array assembly from
        process-local column blocks on a pod)."""
        if jax.process_count() == 1:
            return (
                jax.device_put(images, self._img_sharding),
                jax.device_put(labels, self._lab_sharding),
            )
        w = images.shape[0]
        return (
            jax.make_array_from_process_local_data(
                self._img_sharding, images,
                (w, self.global_batch_size) + images.shape[2:],
            ),
            jax.make_array_from_process_local_data(
                self._lab_sharding, labels, (w, self.global_batch_size),
            ),
        )

    def _index_rows(self, epoch: int, window: int) -> np.ndarray:
        cached = self._epoch_idx
        if cached is None or cached[0] != epoch:
            # benign race with a stale prefetch job: worst case one
            # recompute — the tuple swap below is atomic
            cached = (epoch, epoch_index_matrix(self.loader, epoch))
            self._epoch_idx = cached
        w = self.window_batches
        rows = cached[1][window * w:(window + 1) * w]
        if rows.shape[0] < w:
            # short epoch tail: pad back to the static [W, B] shape with
            # rows the step never slices (epoch_position % W < tail length)
            pad = np.repeat(rows[:1], w - rows.shape[0], axis=0)
            rows = np.concatenate([rows, pad], axis=0)
        return rows

    def _stage(self, epoch: int, window: int):
        """Host-gather one window's rows and start its (async) upload.

        Only THIS process's column block of the window is gathered — on a
        pod each process reads/copies exactly the 1/P of the window its
        own devices will hold (a memmap-backed tree pages only those
        rows), instead of materializing all peers' slices too."""
        # runs on the prefetch thread normally, on the training thread for
        # the first window of an epoch / a resume jump — its own non-main
        # track either way (the main-thread blocking part is what
        # window_swap measures in batch_buffers)
        with tracing.span(
            "window_stage", track="store:stage", epoch=epoch, window=window
        ):
            rows = self._index_rows(epoch, window)
            per_proc = self.global_batch_size // self.loader.process_count
            lo = self.loader.process_index * per_proc
            local_rows = rows[:, lo:lo + per_proc]
            images = np.ascontiguousarray(self.loader.images[local_rows])
            labels = np.ascontiguousarray(
                np.asarray(self.loader.labels)[local_rows].astype(np.int32)
            )
            return self._window_put(images, labels)

    def batch_buffers(self, epoch: int, idx: int) -> Tuple[jax.Array, jax.Array]:
        """The device buffers step ``idx`` of ``epoch`` slices its batch
        from: the window containing ``idx``. Within a window this is the
        cached handle pair (no host work); at a boundary the prefetched
        shadow buffers are swapped in and the NEXT window's staging is
        handed to the prefetch thread. A prefetch exception re-raises here,
        on the training thread, where it can abort the step with a real
        traceback (the EpochLoader worker convention)."""
        window = idx // self.window_batches
        cur = self._current
        if cur is not None and cur[0] == epoch and cur[1] == window:
            return cur[2]
        nxt, self._next = self._next, None
        # window_swap is the main-thread BLOCKING part of the boundary —
        # near-zero when the prefetch won the race, a full synchronous
        # stage when it didn't (the number trace_report attributes to
        # window staging)
        with tracing.span(
            "window_swap", track="main:data", epoch=epoch, window=window,
            prefetched=bool(
                nxt is not None and nxt[0] == epoch and nxt[1] == window
            ),
        ):
            if nxt is not None and nxt[0] == epoch and nxt[1] == window:
                buffers = nxt[2].result()
            else:
                if nxt is not None and not nxt[2].cancel():
                    # a resume/rollback jump abandoned a staged window and
                    # cancel() cannot stop a RUNNING stage: wait it out
                    # (bounded — one window) and free its shard NOW, before
                    # staging the replacement. Letting it drain in the
                    # background would transiently hold a THIRD window shard
                    # on a device the ladder admitted at exactly 2x.
                    try:
                        for arr in nxt[2].result():
                            arr.delete()
                    except Exception:  # noqa: BLE001 — the stale stage itself
                        pass  # failed: nothing landed, nothing to free
                buffers = self._stage(epoch, window)
        self._current = (epoch, window, buffers)
        # Prefetch stays WITHIN the epoch: the first window of each epoch is
        # staged in the caller's thread. That boundary is never hot — every
        # driver drains telemetry collectively (and saves/validates) there —
        # and within-epoch-only staging keeps the upload count per epoch
        # exactly n_windows, which the transfer-count proofs pin.
        if self._executor is not None and window + 1 < self.n_windows:
            self._next = (
                epoch, window + 1,
                self._executor.submit(self._stage, epoch, window + 1),
            )
        return buffers

    def close(self) -> None:
        """Stop the prefetch worker and drop the staged shadow buffers.

        Drivers call this on the way out (their ``finally``, next to the
        EpochLoader ``batches.close()`` hygiene): without it a preemption
        early-exit leaves a live non-daemon prefetch thread whose pending
        window upload — which nothing will ever read — gets joined at
        interpreter exit, stalling the exit-75 path. Queued-but-unstarted
        jobs are cancelled; at most one in-flight stage finishes in the
        background. The store degrades to synchronous staging if used
        again after close (the prefetch=False path)."""
        nxt, self._next = self._next, None
        if nxt is not None:
            nxt[2].cancel()
        if self._executor is not None:
            self._executor.shutdown(wait=False, cancel_futures=True)
            self._executor = None
