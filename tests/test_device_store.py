"""Device-resident data placement (data/device_store.py).

The contract under test is the ISSUE-7 tentpole: with ``--data_placement
device`` every training batch is BYTE-IDENTICAL to what the host
``EpochLoader`` would have produced — full epochs, mid-epoch resume, and the
multi-process slicing — while the hot loop performs exactly ONE host->device
transfer per epoch (the int32 index matrix). All on the virtual 8-device CPU
mesh (conftest.py).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_pytorch_distributed_tpu.data import device_store
from simclr_pytorch_distributed_tpu.data.device_store import (
    DeviceStore,
    epoch_index_matrix,
    resident_bytes_per_device,
    resolve_data_placement,
    slice_epoch_step,
)
from simclr_pytorch_distributed_tpu.data.pipeline import EpochLoader
from simclr_pytorch_distributed_tpu.parallel.mesh import create_mesh
from simclr_pytorch_distributed_tpu.train.supcon_step import epoch_position

pytestmark = pytest.mark.resident


def _dataset(n=70, size=8, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.integers(0, 256, (n, size, size, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, n).astype(np.int32)
    return images, labels


# ------------------------------------------------------------ bit-identity


def test_device_batches_byte_equal_to_host_loader_full_epochs():
    """Every step of two epochs: the resident buffer row equals the host
    loader's batch, bytes and labels alike (the acceptance contract)."""
    images, labels = _dataset()
    loader = EpochLoader(images, labels, 16, base_seed=5)
    mesh = create_mesh()  # the full 8-device virtual mesh
    store = DeviceStore(loader, mesh)
    for epoch in (1, 2):
        ep_imgs, ep_labs = store.epoch_buffers(epoch)
        dev_imgs, dev_labs = np.asarray(ep_imgs), np.asarray(ep_labs)
        assert dev_imgs.dtype == np.uint8 and dev_labs.dtype == np.int32
        host = list(loader.epoch(epoch))
        assert len(host) == loader.steps_per_epoch
        for s, (h_imgs, h_labs) in enumerate(host):
            np.testing.assert_array_equal(dev_imgs[s], h_imgs)
            np.testing.assert_array_equal(dev_labs[s], h_labs)


def test_mid_epoch_resume_is_a_slice_offset_shift():
    """``epoch(e, start_step=k)`` equals the buffer rows from position k on,
    and the in-program position (epoch_position of the restored global step)
    lands exactly there — the resume path never replays consumed batches."""
    images, labels = _dataset()
    loader = EpochLoader(images, labels, 16, base_seed=5)
    mesh = create_mesh()
    store = DeviceStore(loader, mesh)
    steps = loader.steps_per_epoch
    epoch, start_step = 3, 2
    dev_imgs = np.asarray(store.epoch_buffers(epoch)[0])
    resumed = list(loader.epoch(epoch, start_step=start_step))
    assert len(resumed) == steps - start_step
    for off, (h_imgs, _) in enumerate(resumed):
        np.testing.assert_array_equal(dev_imgs[start_step + off], h_imgs)
    # the restored counter maps to the right slice position on device
    gstep = (epoch - 1) * steps + start_step
    pos = int(jax.jit(epoch_position, static_argnums=1)(
        jnp.int32(gstep), steps
    ))
    assert pos == start_step


def test_sliced_step_batch_matches_host_batch_under_jit():
    """The jitted leading-axis slice (what the resident train step runs)
    returns the host loader's exact batch for a traced position."""
    images, labels = _dataset()
    loader = EpochLoader(images, labels, 16, base_seed=9)
    mesh = create_mesh()
    store = DeviceStore(loader, mesh)
    ep_imgs, ep_labs = store.epoch_buffers(1)
    sliced = jax.jit(slice_epoch_step)
    host = list(loader.epoch(1))
    for s, (h_imgs, h_labs) in enumerate(host):
        im, lb = sliced(ep_imgs, ep_labs, jnp.int32(s))
        np.testing.assert_array_equal(np.asarray(im), h_imgs)
        np.testing.assert_array_equal(np.asarray(lb), h_labs)


def test_multi_process_virtual_mesh_slices_match_per_process_loaders():
    """Multi-host layout: column block p of the index matrix IS process p's
    ``EpochLoader`` stream, so a mesh whose data axis spans processes gives
    each process's devices exactly its host-loader slice of every global
    batch (the virtual-mesh stand-in for a real pod run, which
    tests/test_multiprocess.py covers end-to-end)."""
    images, labels = _dataset(n=64)
    nproc, global_batch = 4, 16
    per_proc = global_batch // nproc
    ref = EpochLoader(images, labels, global_batch, base_seed=3)
    idx = epoch_index_matrix(ref, epoch=5)
    assert idx.shape == (ref.steps_per_epoch, global_batch)
    for p in range(nproc):
        shard_loader = EpochLoader(
            images, labels, global_batch, base_seed=3,
            process_index=p, process_count=nproc,
        )
        for s, (h_imgs, h_labs) in enumerate(shard_loader.epoch(5)):
            cols = idx[s, p * per_proc:(p + 1) * per_proc]
            np.testing.assert_array_equal(images[cols], h_imgs)
            np.testing.assert_array_equal(labels[cols], h_labs)


# ------------------------------------------------------- transfer counting


def test_one_index_upload_per_epoch():
    """The per-epoch H2D is ONE index-matrix transfer: repeated buffer
    requests for the same epoch hit the cache; a new epoch uploads once."""
    images, labels = _dataset()
    loader = EpochLoader(images, labels, 16, base_seed=5)
    mesh = create_mesh()
    uploads = []

    def counting_put(idx):
        uploads.append(idx.nbytes)
        return jax.device_put(idx)

    store = DeviceStore(loader, mesh, index_put=counting_put)
    store.epoch_buffers(1)
    store.epoch_buffers(1)
    store.epoch_buffers(1)
    assert len(uploads) == 1
    b1 = store.epoch_buffers(2)
    assert len(uploads) == 2
    assert b1 is store.epoch_buffers(2)  # cached object, no regather
    # and the transfer really is the tiny index vector, not the data
    assert uploads[0] == loader.steps_per_epoch * 16 * 4  # int32


# ------------------------------------------------------ placement resolve


def test_resolve_placement_host_and_device_pass_through():
    images, labels = _dataset()
    mesh = create_mesh()
    assert resolve_data_placement("host", images, labels, 16, mesh) == "host"
    assert resolve_data_placement(
        "device", images, labels, 16, mesh, budget_bytes=1 << 30
    ) == "device"
    with pytest.raises(ValueError, match="unknown data_placement"):
        resolve_data_placement("hbm", images, labels, 16, mesh)


def test_resolve_auto_falls_back_over_budget_with_banner(caplog):
    images, labels = _dataset()
    mesh = create_mesh()
    with caplog.at_level(logging.WARNING, logger="simclr_pytorch_distributed_tpu.data.device_store"):
        got = resolve_data_placement(
            "auto", images, labels, 16, mesh, budget_bytes=10
        )
    assert got == "host"
    assert any("auto -> host" in r.message for r in caplog.records)
    # explicit 'device' over budget fails loudly at startup, never OOMs
    with pytest.raises(ValueError, match="cannot be satisfied"):
        resolve_data_placement(
            "device", images, labels, 16, mesh, budget_bytes=10
        )


def test_resolve_never_makes_a_memmap_resident(tmp_path):
    """A memmap-backed dataset disqualifies RESIDENCY on every path
    (paging the whole tree into RAM/HBM): explicit 'device' raises, and
    'auto' walks the ladder past the resident rung — to 'window' when the
    double-buffered window fits (tests/test_window_store.py proves the
    windowed contract), to 'host' when nothing does."""
    images, labels = _dataset()
    mm_path = tmp_path / "imgs.npy"
    np.save(mm_path, images)
    mm = np.load(mm_path, mmap_mode="r")
    mesh = create_mesh()
    assert isinstance(mm, np.memmap)
    assert resolve_data_placement(
        "auto", mm, labels, 16, mesh, budget_bytes=1 << 30
    ) == "window"
    assert resolve_data_placement(
        "auto", mm, labels, 16, mesh, budget_bytes=10
    ) == "host"
    with pytest.raises(ValueError, match="memmap"):
        resolve_data_placement(
            "device", mm, labels, 16, mesh, budget_bytes=1 << 30
        )
    # the PRODUCTION path: EpochLoader's ascontiguousarray strips the
    # np.memmap subclass into a plain ndarray VIEW (no copy — base chain
    # still ends at the on-disk file); make_store must still see through
    # it, or residency would silently page the whole tree into RAM/HBM
    loader = EpochLoader(mm, labels, 16, base_seed=0)
    assert not isinstance(loader.images, np.memmap)
    assert device_store._is_memmap_backed(loader.images)
    store = device_store.make_store(
        "auto", loader, mesh, budget_bytes=1 << 30
    )
    assert not isinstance(store, DeviceStore)


def test_resident_bytes_accounting():
    """dataset (replicated) + 2x the sharded drop_last epoch buffer."""
    images, labels = _dataset(n=70)
    row = images[0].nbytes + 4
    used = (70 // 16) * 16
    assert resident_bytes_per_device(images, labels, 16, 1) == (
        70 * row + 2 * used * row
    )
    # 8-way sharding divides only the buffer term
    assert resident_bytes_per_device(images, labels, 16, 8) == (
        70 * row + 2 * ((used * row + 7) // 8)
    )


def test_store_rejects_bad_geometry():
    images, labels = _dataset(n=70)
    mesh = create_mesh()  # data axis = 8
    ragged = EpochLoader(images, labels, 16, drop_last=False, shuffle=False)
    with pytest.raises(ValueError, match="drop_last"):
        DeviceStore(ragged, mesh)
    indivisible = EpochLoader(images, labels, 12, base_seed=0)
    with pytest.raises(ValueError, match="divisible"):
        DeviceStore(indivisible, mesh)


def test_make_store_resolves_from_the_loader_itself():
    """The drivers' one-call entry point: what resolution inspects must be
    exactly what the store would upload (the loader's own arrays), and the
    store/None contract follows the verdict."""
    images, labels = _dataset()
    mesh = create_mesh()
    loader = EpochLoader(images, labels, 16, base_seed=3)
    store = device_store.make_store("auto", loader, mesh,
                                    budget_bytes=1 << 30)
    assert store is not None and store.loader is loader
    assert device_store.make_store("auto", loader, mesh,
                                   budget_bytes=10) is None
    assert device_store.make_store("host", loader, mesh) is None


def test_device_budget_bytes_falls_back_without_memory_stats():
    # CPU devices report no memory stats -> the fixed conservative default
    assert device_store.device_budget_bytes() == device_store.DEFAULT_BUDGET_BYTES


class _FakeTpu:
    platform = "tpu"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 5}])
def test_device_budget_bytes_raises_on_a_tpu_without_stats(monkeypatch, stats):
    """No fallback that hides the device: a TPU that cannot say how much
    memory it has is an error, not DEFAULT_BUDGET_BYTES."""
    monkeypatch.setattr(
        device_store.jax, "local_devices", lambda: [_FakeTpu(stats)]
    )
    with pytest.raises(RuntimeError, match="bytes_limit"):
        device_store.device_budget_bytes()


def test_device_budget_bytes_is_a_fraction_of_free_tpu_memory(monkeypatch):
    monkeypatch.setattr(
        device_store.jax, "local_devices",
        lambda: [_FakeTpu({"bytes_limit": 1000, "bytes_in_use": 200})],
    )
    assert device_store.device_budget_bytes(fraction=0.5) == 400


def test_resolve_placement_verdict_is_collective(monkeypatch, caplog):
    """The budget reads LOCAL memory_stats, but placement selects which
    collective programs a process runs — a split verdict across hosts would
    deadlock the pod at the first epoch's gather. One over-budget peer must
    send EVERY process to host placement ('auto') or raise on every process
    (explicit 'device')."""
    images, labels = _dataset()
    mesh = create_mesh()
    calls = []

    def peer_disagrees(local_ok):
        calls.append(local_ok)
        return False  # some OTHER process was over budget; we were fine

    monkeypatch.setattr(
        device_store, "_agree_across_processes", peer_disagrees
    )
    with caplog.at_level(logging.WARNING, logger="simclr_pytorch_distributed_tpu.data.device_store"):
        got = resolve_data_placement(
            "auto", images, labels, 16, mesh, budget_bytes=1 << 30
        )
    assert got == "host"
    # 'auto' walks BOTH ladder rungs as matched collective points (the
    # rung-1 result is identical everywhere, so every process proceeds to
    # rung 2 together); our local verdict was 'fits' at each
    assert calls == [True, True]
    assert any("peer process" in r.message for r in caplog.records)
    calls.clear()
    with pytest.raises(ValueError, match="peer process"):
        resolve_data_placement(
            "device", images, labels, 16, mesh, budget_bytes=1 << 30
        )
    assert calls == [True]  # explicit 'device': one collective point
    # each collective point is entered with the LOCAL verdict — a locally
    # over-budget process still participates in the allgathers (matched
    # schedules) before taking its reject path
    calls.clear()
    with caplog.at_level(logging.WARNING, logger="simclr_pytorch_distributed_tpu.data.device_store"):
        got = resolve_data_placement(
            "auto", images, labels, 16, mesh, budget_bytes=10
        )
    assert got == "host" and calls == [False, False]
