"""Runtime / mesh layer — the TPU-native replacement for the reference's L1.

The reference initializes one NCCL process per GPU via ``torch.distributed.launch``
(``main_supcon.py:359-364``) and weaves collectives through DDP/SyncBN. Here the
runtime is a single SPMD program:

- one process per HOST (not per chip); ``jax.distributed.initialize()`` for
  multi-host rendezvous (replaces the env:// MASTER_ADDR/PORT dance);
- a ``jax.sharding.Mesh`` whose ``data`` axis spans every chip; collectives ride
  ICI within a slice and DCN across slices, chosen by XLA from the shardings;
- a second ``model`` axis is supported for future tensor-parallel layouts — the
  reference has no model parallelism (SURVEY.md §2.2) so it defaults to size 1.

"rank 0"-style I/O gating (reference ``main_supcon.py:137-148,327,397``) becomes
``is_main_process()`` == ``jax.process_index() == 0``.
"""

from __future__ import annotations

import contextlib
import logging
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

DATA_AXIS = "data"
MODEL_AXIS = "model"


_backend_asked = False


def _backend_ask():
    """Around each of the program's asks of the backend that can be its
    first (``setup_distributed``, ``create_mesh`` without devices,
    ``is_main_process``, which flag parsing calls): the process's first is
    the set-up span ``backend_start`` (track ``setup``), which starts the
    backend (on a TPU host, the chips' runtime) unless something outside
    the program did, and closes the span ``import`` before it; every later
    one is a null context."""
    global _backend_asked
    if _backend_asked:
        return contextlib.nullcontext()
    _backend_asked = True
    # lazy: utils imports this module
    from simclr_pytorch_distributed_tpu.utils import tracing

    tracing.imports_done()
    return tracing.span("backend_start", track=tracing.SETUP_TRACK)


def setup_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host rendezvous (replaces init_process_group, main_supcon.py:359-364).

    No-op on a single host with no coordinator configured. On TPU pods the
    arguments are normally inferred from the environment, so a bare
    ``setup_distributed()`` suffices.
    """
    if coordinator_address is None:
        # No explicit coordinator: either the runtime was already initialized
        # by a launcher wrapper (process_count > 1 — initialize() would
        # raise), or this is a plain single-host run (nothing to do). Only
        # this branch may touch process_count(): the explicit-coordinator
        # path below must reach initialize() before any backend init.
        with _backend_ask():
            n = jax.process_count()
        if n > 1 or num_processes in (None, 1):
            return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    logger.info(
        "distributed: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )


def create_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    model_parallel: int = 1,
    axis_names: Sequence[str] = (DATA_AXIS, MODEL_AXIS),
) -> Mesh:
    """Build a (data, model) mesh over all devices; model axis defaults to 1."""
    if devices is None:
        with _backend_ask():
            devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    if n % model_parallel != 0:
        raise ValueError(f"{n} devices not divisible by model_parallel={model_parallel}")
    dev_array = np.array(devices).reshape(n // model_parallel, model_parallel)
    return Mesh(dev_array, tuple(axis_names))


def broadcast_from_main(s: str, max_len: int = 512) -> str:
    """Every process adopts process 0's value of a small string.

    Run/checkpoint folder names embed a minute-resolution wall-clock
    timestamp derived independently on each process (config parity with the
    reference); with collective orbax saves the folder must agree across
    hosts, so clock skew across a minute boundary would corrupt checkpoints.
    No-op on a single process.
    """
    if jax.process_count() == 1:
        return s
    from jax.experimental import multihost_utils

    buf = np.zeros(max_len, np.uint8)
    raw = s.encode()
    if len(raw) > max_len:
        raise ValueError(f"string too long to broadcast ({len(raw)} > {max_len})")
    buf[: len(raw)] = np.frombuffer(raw, np.uint8)
    out = multihost_utils.broadcast_one_to_all(buf)
    # cast by VALUE, not raw memory: the broadcast can return the uint8
    # payload in a widened dtype (observed with gloo CPU collectives),
    # and bytes() of that buffer interleaves every char with nulls
    out = np.asarray(out).astype(np.uint8)
    return out.tobytes().rstrip(b"\x00").decode()


def sync_processes(tag: str) -> None:
    """Cross-process barrier before exit paths.

    In a multi-host job, process 0 finishes slow end-of-run I/O (final orbax
    save, meter drains) AFTER the other processes fall off the epoch loop; if
    they exit immediately, the JAX coordination-service shutdown barrier times
    out and every process dies with a spurious INTERNAL error. One explicit
    sync keeps all processes alive until the slowest is done. No-op on a
    single process.
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)


def is_main_process() -> bool:
    """Process-0 gating for I/O (reference local_rank==0 checks)."""
    with _backend_ask():
        return jax.process_index() == 0


def batch_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard the leading (batch) dim over 'data'; replicate everything else."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def epoch_buffer_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Layout of a device-resident ``[steps, batch, ...]`` epoch buffer
    (data/device_store.py): the BATCH dim sharded over 'data', the steps dim
    replicated. Each device therefore holds its own batch slice of EVERY
    step, so the per-step ``lax.dynamic_slice`` on the leading axis is a
    purely local slice — no communication in the hot loop — and on a
    multi-host mesh each process's devices hold exactly that process's
    ``EpochLoader`` slice of every global batch. The windowed store's
    ``[window_batches, batch, ...]`` buffers use the same convention (the
    leading dim is just shorter), so one compiled step layout serves both
    resident shapes."""
    if ndim < 2:
        raise ValueError(f"epoch buffers are [steps, batch, ...]; got ndim={ndim}")
    return NamedSharding(mesh, P(None, DATA_AXIS, *([None] * (ndim - 2))))


def batch_sharding_if_divisible(mesh: Mesh, batch: int, ndim: int = 1) -> NamedSharding:
    """Batch sharding when the size divides the 'data' axis, else replicated.

    GSPMD requires the sharded dim to divide the axis; serving-style
    callers with a FIXED small batch (the engine's jit buckets,
    serve/engine.py) want "shard when it fits, fall back to one-device
    replication when it doesn't" rather than an error — a bucket of 1 on an
    8-chip mesh is a latency path, not a mistake.
    """
    if batch % mesh.shape.get(DATA_AXIS, 1) == 0:
        return batch_sharding(mesh, ndim)
    return replicated_sharding(mesh)


def put_batch_if_divisible(mesh: Mesh, x: np.ndarray) -> jax.Array:
    """Dispatch-stage H2D: place a host batch under the bucket layout NOW.

    The serving engine's dispatch/completion split (serve/engine.py) wants
    the host->device transfer to happen AT DISPATCH — owned by the stage
    that runs while earlier batches are still computing — rather than
    implicitly inside the jitted call's argument handling at whatever moment
    the call is reached. ``device_put`` starts the transfer asynchronously
    and returns immediately; the array lands already laid out as the bucket
    program's ``in_shardings`` expects, so the call commits no further
    host work and XLA never re-shards.
    """
    return jax.device_put(
        x, batch_sharding_if_divisible(mesh, int(x.shape[0]), np.ndim(x))
    )


def tp_leaf_spec(shape, model_size: int, min_last: int = 64) -> P:
    """Channel-wise tensor-parallel spec for one state leaf.

    Shards the trailing (output-channel / feature) axis over 'model' when it
    divides evenly and is large enough to be worth splitting. Applied uniformly
    to params, BN running stats, and optimizer momentum (their shapes mirror
    the params), so the whole train state partitions consistently; GSPMD
    propagates the layouts through convs/matmuls and inserts the tensor-parallel
    collectives. With model_size == 1 everything is replicated (the default —
    the reference has no model parallelism, SURVEY.md §2.2).
    """
    if (
        model_size > 1
        and len(shape) > 0
        and shape[-1] % model_size == 0
        and shape[-1] >= min_last
    ):
        return P(*([None] * (len(shape) - 1)), MODEL_AXIS)
    return P()


def state_sharding(mesh: Mesh, state) -> "jax.tree_util.PyTreeDef":
    """NamedSharding tree for a TrainState-like pytree under the mesh's
    (data, model) layout: batch-independent state is model-axis sharded by
    ``tp_leaf_spec`` and replicated over 'data'."""
    model_size = mesh.shape.get(MODEL_AXIS, 1)

    def leaf(x):
        shape = getattr(x, "shape", ())
        return NamedSharding(mesh, tp_leaf_spec(tuple(shape), model_size))

    return jax.tree.map(leaf, state)


def shard_host_batch(batch, mesh: Mesh):
    """Place a host batch onto the mesh, sharded along 'data'.

    Single-host: a plain ``device_put`` with the batch sharding (the whole array
    is local). Multi-host: each process holds its own shard of the global batch
    (the ``DistributedSampler`` equivalent lives in data/pipeline.py) and the
    global array is assembled from process-local data.
    """
    def put(x):
        sharding = batch_sharding(mesh, np.ndim(x))
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        return jax.make_array_from_process_local_data(sharding, np.asarray(x))

    return jax.tree.map(put, batch)
