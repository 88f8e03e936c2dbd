"""Zero-sync telemetry: mechanical proofs for the device-side metric ring +
background flush executor (ops/metrics.MetricRing, utils/telemetry.py).

The claims are tested, not assumed:

- OVERLAP: with the async executor, step k+1 dispatches while flush k is
  still in flight (the fake transfer is gated on an Event); the sync control
  provably never does.
- ONE TRANSFER: a flush performs exactly one host transfer per window
  (instrumented injectable device_get), regardless of steps or key count.
- WRAPAROUND: epoch tails shorter than the window, and windows that start at
  a non-zero ``step % window`` (mid-epoch resume / print_freq not dividing
  steps_per_epoch), resolve the right rows.
- FAILURE: a worker-side NonFiniteLossError re-raises on the MAIN thread at
  the next boundary, and the executor stays usable afterwards (the rollback
  policy keeps training).
- PREEMPTION: the boundary preemption decision is taken on the main thread
  while a flush is still in flight; draining then completes the meters.
- EQUIVALENCE: the async path produces the identical TB stream
  (tags x steps x values) as the sync path. The fast test drives the loop
  shape directly; the slow tests run all three REAL trainers both ways.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_pytorch_distributed_tpu.ops.metrics import MetricRing
from simclr_pytorch_distributed_tpu.utils import preempt
from simclr_pytorch_distributed_tpu.utils.guard import NonFiniteLossError
from simclr_pytorch_distributed_tpu.utils.telemetry import (
    FlushExecutor,
    TelemetryFlushError,
    TelemetrySession,
)

KEYS = ("loss", "m1")


def _metrics(loss, m1=0.0):
    return {"loss": jnp.float32(loss), "m1": jnp.float32(m1)}


def _drive(session, n_steps, window, events=None, loss_of=float):
    """The drivers' loop shape: write -> append -> boundary submit.

    ``events`` (a list) records the interleaving: ``dispatch k`` when step k
    runs, ``flush done @k`` when the window job ending at step k completes.
    Returns the fetched rows in flush order.
    """
    out = []
    ring_buf = session.init_buffer()
    for step in range(n_steps):
        if events is not None:
            events.append(f"dispatch {step}")
        ring_buf = session.ring.write(ring_buf, _metrics(loss_of(step)), step)
        session.append(step, step)
        if (step + 1) % window == 0 or step + 1 == n_steps:
            boundary = step

            def consume(fetched, boundary=boundary):
                out.extend(fetched)
                if events is not None:
                    events.append(f"flush done @{boundary}")

            session.submit_window(ring_buf, consume)
    session.drain()
    return out


def test_async_overlap_sync_control():
    """Step k+1 dispatches while flush k is in flight under async; the sync
    control completes flush k BEFORE any later dispatch. Same loop, same
    gated transfer — only the executor mode differs."""
    n_steps, window = 6, 2

    def make_gated(release):
        def gated_get(x):
            release.wait(timeout=10)
            return jax.device_get(x)

        return gated_get

    # async arm: hold every flush hostage; the loop must keep going anyway.
    # Drive the loop in a worker so the main thread can assert mid-flight.
    release = threading.Event()
    events = []
    session = TelemetrySession(window, KEYS, "async", device_get=make_gated(release))
    result = {}
    loop = threading.Thread(
        target=lambda: result.update(rows=_drive(session, n_steps, window, events)),
        daemon=True,
    )
    loop.start()
    # the loop can only finish dispatching everything if no flush blocks it
    for _ in range(200):
        if sum(e.startswith("dispatch") for e in events) == n_steps:
            break
        time.sleep(0.01)
    dispatched_while_gated = sum(e.startswith("dispatch") for e in events)
    flushes_done_while_gated = sum(e.startswith("flush done") for e in events)
    release.set()
    loop.join(timeout=10)
    assert not loop.is_alive()
    session.close()
    assert dispatched_while_gated == n_steps  # dispatch ran ahead of flush 0
    assert flushes_done_while_gated == 0  # while every flush was still gated
    assert [i for i, _ in result["rows"]] == list(range(n_steps))

    # sync control: the gate must be OPEN or the loop deadlocks — which is
    # itself the proof that sync flushes block dispatch; run it open and
    # assert the interleaving is strictly flush-before-next-dispatch
    release2 = threading.Event()
    release2.set()
    events2 = []
    control = TelemetrySession(window, KEYS, "sync", device_get=make_gated(release2))
    _drive(control, n_steps, window, events2)
    control.close()
    for boundary in range(window - 1, n_steps, window):
        flush_pos = events2.index(f"flush done @{boundary}")
        later_dispatches = [
            e for e in events2[:flush_pos] if e.startswith("dispatch")
        ]
        # every dispatch that happened before this flush belongs to steps
        # <= boundary: the sync path NEVER runs ahead of an open flush
        assert all(int(e.split()[1]) <= boundary for e in later_dispatches)


def test_flush_is_exactly_one_transfer_per_window():
    calls = []

    def counting_get(x):
        calls.append(1)
        return jax.device_get(x)

    session = TelemetrySession(5, KEYS, "sync", device_get=counting_get)
    rows = _drive(session, 15, 5)  # 3 full windows
    session.close()
    assert len(calls) == 3
    assert session.ring.transfers == 3
    assert len(rows) == 15


def test_ring_wraparound_tail_and_unaligned_windows():
    """7 steps through a window of 5 (tail shorter than the window, slots
    wrapping 5->0, 6->1), then a window starting at step%window != 0 (the
    supcon epoch-2 shape when print_freq doesn't divide steps_per_epoch)."""
    session = TelemetrySession(5, KEYS, "sync")
    rows = _drive(session, 7, 5, loss_of=lambda s: 10.0 + s)
    assert [(i, m["loss"]) for i, m in rows] == [
        (s, 10.0 + s) for s in range(7)
    ]

    # unaligned continuation: steps 7..10 in one window (slots 2,3,4,0)
    ring_buf = session.init_buffer()
    out = []
    for step in range(7, 11):
        ring_buf = session.ring.write(ring_buf, _metrics(100.0 + step), step)
        session.append(step, step)
    session.submit_window(ring_buf, out.extend)
    session.drain()
    session.close()
    assert [(i, m["loss"]) for i, m in out] == [
        (s, 100.0 + s) for s in range(7, 11)
    ]


def test_ring_overflow_and_key_mismatch_raise():
    ring = MetricRing(2, KEYS)
    ring.append(0, 0)
    ring.append(1, 1)
    with pytest.raises(RuntimeError, match="overflow"):
        ring.append(2, 2)
    with pytest.raises(ValueError, match="metric keys"):
        ring.write(ring.init_buffer(), {"loss": jnp.float32(0)}, 0)
    with pytest.raises(ValueError, match="window"):
        MetricRing(0, KEYS)


def test_worker_exception_surfaces_on_main_thread_then_executor_reusable():
    """The NaN guard runs in the window job: its NonFiniteLossError must
    re-raise on the main thread at the next boundary, discard any queued
    poisoned jobs, and leave the executor usable (rollback continues)."""
    ex = FlushExecutor("async")
    ran = []

    def bad_job():
        raise NonFiniteLossError(float("nan"), 7)

    ex.submit(bad_job)
    ex.submit(lambda: ran.append("poisoned"))  # queued after the failure
    with pytest.raises(NonFiniteLossError, match="step 7"):
        ex.drain()
    assert ran == []  # the queued job post-dating the failure was discarded
    ex.submit(lambda: ran.append("after"))  # the executor recovered
    ex.drain()
    assert ran == ["after"]
    ex.close()


def test_check_failures_global_drains_and_raises_at_boundary():
    """The drivers' collective failure observation: a pending worker
    failure raises at the NEXT deterministic boundary (single-process
    short-circuits the allgather), and submit() itself never raises — the
    raise point must not depend on per-host flush scheduling."""
    session = TelemetrySession(2, KEYS, "async")
    ring_buf = session.init_buffer()
    session.ring.write(ring_buf, _metrics(0.0), 0)
    session.append(0, 0)

    def bad_consume(fetched):
        raise NonFiniteLossError(float("nan"), 3)

    session.submit_window(ring_buf, bad_consume)
    # let the worker actually fail, then submit another window: no raise here
    session.executor.wait_idle()
    session.ring.write(ring_buf, _metrics(1.0), 1)
    session.append(1, 1)
    session.submit_window(ring_buf, lambda rows: None)
    with pytest.raises(NonFiniteLossError, match="step 3"):
        session.check_failures_global(step_hint=1)
    session.check_failures_global()  # cleared: the executor is reusable
    session.close()


def test_check_failures_global_skew_guard(monkeypatch):
    """A host whose OWN windows were clean but whose peer flagged a failure
    must still leave the loop, with the exception type the allgathered code
    names: NonFiniteLossError for a NaN peer, TelemetryFlushError for a
    non-NaN flush failure."""
    session = TelemetrySession(2, KEYS, "async")
    monkeypatch.setattr(session, "_failure_code", lambda: 1)
    with pytest.raises(NonFiniteLossError):
        session.check_failures_global(step_hint=7)
    monkeypatch.setattr(session, "_failure_code", lambda: 2)
    with pytest.raises(TelemetryFlushError):
        session.check_failures_global(step_hint=7)
    session.close()


def test_late_local_failure_exits_with_allgathered_type(monkeypatch):
    """The exit type is a pure function of the ALLGATHERED code: a local
    failure that lands AFTER the code exchange (the window was still in
    flight at the snapshot) must not reclassify the exit. Simulated here:
    the collective code says 1 (a peer's NaN) while this host's drain
    surfaces a TB-style IOError — the host must leave through the NaN
    policy like its peers, with the local error chained as __cause__."""
    session = TelemetrySession(2, KEYS, "async")
    ring_buf = session.init_buffer()
    session.ring.write(ring_buf, _metrics(0.0), 0)
    session.append(0, 0)

    def late_disk_error(fetched):
        raise OSError("No space left on device")

    session.submit_window(ring_buf, late_disk_error)
    session.executor.wait_idle()
    # as-if the allgather ran while this host's job was still in flight
    # (local snapshot 0) and a peer reported a non-finite loss (max = 1)
    monkeypatch.setattr(session, "_failure_code", lambda: 1)
    with pytest.raises(NonFiniteLossError) as ei:
        session.check_failures_global(step_hint=9)
    assert isinstance(ei.value.__cause__, OSError)
    session.close()


def test_non_nan_flush_failure_never_triggers_nan_policy():
    """A TB-write IOError (or any non-NaN job failure) must surface as
    TelemetryFlushError — NOT NonFiniteLossError — or --nan_policy rollback
    would discard clean epochs over a disk error. The original exception
    rides as __cause__ and the executor is clean afterwards."""
    session = TelemetrySession(2, KEYS, "async")
    ring_buf = session.init_buffer()
    session.ring.write(ring_buf, _metrics(0.0), 0)
    session.append(0, 0)

    def disk_full(fetched):
        raise OSError("No space left on device")

    session.submit_window(ring_buf, disk_full)
    session.executor.wait_idle()
    with pytest.raises(TelemetryFlushError) as ei:
        session.check_failures_global(step_hint=5)
    assert isinstance(ei.value.__cause__, OSError)
    session.check_failures_global()  # cleared: the executor is reusable
    session.close()


def test_drain_global_waits_then_raises_classified_type():
    """The drivers' pre-collective-save drain: completes all jobs WITHOUT a
    host-local raise, then surfaces the failure through the collective
    observation with its classified type — so every host's raise point (and
    type) stays matched ahead of a collective checkpoint save. An empty
    trailing submit_window is never a raise point either."""
    session = TelemetrySession(2, KEYS, "async")
    ring_buf = session.init_buffer()
    session.ring.write(ring_buf, _metrics(0.0), 0)
    session.append(0, 0)
    gate = threading.Event()

    def slow_nan(fetched):
        gate.wait(timeout=5)
        raise NonFiniteLossError(float("nan"), 0)

    session.submit_window(ring_buf, slow_nan)
    session.submit_window(ring_buf, lambda rows: None)  # empty: no raise
    gate.set()
    with pytest.raises(NonFiniteLossError):
        session.drain_global(step_hint=0)
    session.drain_global()  # cleared: reusable
    session.close()


def test_trailing_submit_clears_short_epoch_pending():
    """Steps left pending by an epoch shorter than expected must not leak
    into the next epoch's windows (ring bookkeeping is session-lifetime):
    the drivers' trailing submit_window flushes them."""
    session = TelemetrySession(5, KEYS, "sync")
    out = []
    ring_buf = session.init_buffer()
    for step in range(3):  # "epoch" ends before any boundary fires
        ring_buf = session.ring.write(ring_buf, _metrics(step), step)
        session.append(step, step)
    session.submit_window(ring_buf, out.extend)  # the trailing call
    session.drain()
    assert [i for i, _ in out] == [0, 1, 2]
    assert session.ring.take_window() == []  # nothing stale for epoch 2
    session.close()


def test_sync_mode_defers_failure_like_async():
    """Sync mode runs jobs inline but failures follow the SAME deferred
    protocol as async — stored, not raised out of submit (a raw raise would
    skip the collective failure-code exchange and exit with the wrong type),
    then surfaced by poll/drain/check_failures_global at the boundary."""
    ex = FlushExecutor("sync")
    ran = []
    ex.submit(lambda: (_ for _ in ()).throw(NonFiniteLossError(0.0, 1)))
    ex.submit(lambda: ran.append(1))  # poisoned: discarded like async
    assert ran == []
    with pytest.raises(NonFiniteLossError):
        ex.poll()
    ex.submit(lambda: ran.append(2))  # clean again after poll
    assert ran == [2]
    ex.drain()  # no-op, clean
    ex.close()


def test_preemption_decided_while_flush_in_flight():
    """The collective preemption decision runs on the MAIN thread at the
    boundary — it never waits for the in-flight D2H; draining afterwards
    completes the meters before the emergency save would read them."""
    release = threading.Event()
    fetched = []

    def gated_get(x):
        release.wait(timeout=10)
        return jax.device_get(x)

    session = TelemetrySession(2, KEYS, "async", device_get=gated_get)
    ring_buf = session.init_buffer()
    for step in range(2):
        ring_buf = session.ring.write(ring_buf, _metrics(step), step)
        session.append(step, step)
    session.submit_window(ring_buf, fetched.extend)  # in flight, gated

    preempt.request()
    try:
        # the decision completes while the flush is STILL gated
        assert preempt.requested_global()
        assert fetched == []
    finally:
        preempt.uninstall()
    release.set()
    session.drain()
    session.close()
    assert [i for i, _ in fetched] == [0, 1]  # meters complete post-drain


def test_tb_stream_equivalent_sync_vs_async():
    """Same loop, same values: the async arm's (tag, step, value) stream is
    identical to the sync arm's — ordering included (jobs are FIFO on one
    worker)."""

    def run(mode):
        stream = []
        session = TelemetrySession(3, KEYS, mode)
        rows = _drive(
            session, 8, 3, loss_of=lambda s: float(np.sin(s))
        )
        for i, m in rows:
            stream.append(("info/loss", i, m["loss"]))
        session.close()
        return stream

    assert run("sync") == run("async")


# ---------------------------------------------------------------------------
# driver-level equivalence: the three REAL trainers, sync vs async telemetry
# ---------------------------------------------------------------------------

SIZE = 8


class RecordingTB:
    """TBLogger stand-in: records (tag, value, step) on every process."""

    last_stream = None

    def __init__(self, logdir, enabled=True):
        self.records = []
        RecordingTB.last_stream = self.records

    def log_value(self, tag, value, step):
        self.records.append((tag, float(value), int(step)))

    def close(self):
        pass


@pytest.fixture
def tiny_drivers(monkeypatch):
    import jax as _jax

    from simclr_pytorch_distributed_tpu.data import cifar as cifar_lib
    from simclr_pytorch_distributed_tpu.parallel import mesh as mesh_lib
    from simclr_pytorch_distributed_tpu.train import ce as ce_driver
    from simclr_pytorch_distributed_tpu.train import linear as linear_driver
    from simclr_pytorch_distributed_tpu.train import supcon as supcon_driver

    orig = cifar_lib.synthetic_dataset

    def small(n=2048, num_classes=10, seed=0, size=32):
        return orig(n=200, num_classes=num_classes, seed=seed, size=SIZE)

    monkeypatch.setattr(cifar_lib, "synthetic_dataset", small)

    def limited_create_mesh(devices=None, **kw):
        if devices is None:
            devices = _jax.devices()[:1]
        return mesh_lib.create_mesh(devices=devices, **kw)

    for driver in (supcon_driver, linear_driver, ce_driver):
        monkeypatch.setattr(driver, "create_mesh", limited_create_mesh)
        monkeypatch.setattr(driver, "TBLogger", RecordingTB)
    return supcon_driver, linear_driver, ce_driver


def _tb_ab(run_fn):
    """Run a driver twice (sync then async telemetry); return both streams."""
    streams = {}
    for mode in ("sync", "async"):
        run_fn(mode)
        streams[mode] = list(RecordingTB.last_stream)
    return streams


@pytest.mark.parametrize("placement", ["host", "device", "window"])
def test_all_drivers_flush_boundary_smoke(tmp_path, tiny_drivers, placement):
    """FAST guard on the driver<->flush_boundary contract: one sync-mode
    epoch through each REAL trainer. Sync telemetry runs every window job
    inline, so a driver whose ``consume`` signature diverges from what
    ``flush_boundary`` calls (one arg vs the ``(fetched, bt)`` pair when
    ``batch_meter`` is given) raises a ``TypeError`` right here instead of
    only in the slow-marked equivalence tests the default suite deselects.

    Parametrized over ``--data_placement`` so EVERY driver loop stays under
    driver-level test: 'device' is the HBM-resident branch, 'window' the
    streaming window-store branch (data_window_batches=2 forces real
    mid-epoch window swaps in the 5-step epoch), 'host' the per-step H2D
    branch (the production path for over-budget datasets — 'auto' alone
    would always resolve to 'device' on the tiny in-RAM synthetic set and
    leave the other loops covered only at the data-layer)."""
    supcon_driver, linear_driver, ce_driver = tiny_drivers
    from simclr_pytorch_distributed_tpu import config as config_lib

    cfg = config_lib.SupConConfig(
        model="resnet10", dataset="synthetic", batch_size=32, epochs=1,
        learning_rate=0.05, cosine=True, save_freq=5, print_freq=2,
        size=SIZE, workdir=str(tmp_path / "sc"), seed=0, method="SimCLR",
        telemetry="sync", data_placement=placement, data_window_batches=2,
    )
    supcon_driver.run(config_lib.finalize_supcon(cfg))
    assert any(r[0].startswith("info/") for r in RecordingTB.last_stream)
    for driver, prefix, sub in ((linear_driver, "", "lin"), (ce_driver, "ce_", "ce")):
        lcfg = config_lib.LinearConfig(
            model="resnet10", dataset="synthetic", batch_size=32, epochs=1,
            learning_rate=0.1, size=SIZE, val_batch_size=40,
            workdir=str(tmp_path / sub), print_freq=2, telemetry="sync",
            data_placement=placement, data_window_batches=2,
        )
        driver.run(config_lib.finalize_linear(lcfg, prefix=prefix) if prefix
                   else config_lib.finalize_linear(lcfg))


def test_supcon_tb_stream_bitwise_equal(tmp_path, tiny_drivers):
    supcon_driver, _, _ = tiny_drivers
    from simclr_pytorch_distributed_tpu import config as config_lib

    def go(mode):
        cfg = config_lib.SupConConfig(
            model="resnet10", dataset="synthetic", batch_size=32, epochs=2,
            learning_rate=0.05, cosine=True, save_freq=5,
            print_freq=2, size=SIZE, workdir=str(tmp_path / mode), seed=0,
            method="SimCLR", telemetry=mode,
        )
        supcon_driver.run(config_lib.finalize_supcon(cfg))

    streams = _tb_ab(go)
    # per-iter info/* tags at EVERY step + epoch tags, bit-for-float equal;
    # 200-sample synthetic: 160 train -> 5 steps/epoch (windows 2+2+1 tail)
    assert streams["sync"] == streams["async"]
    info_tags = [r for r in streams["sync"] if r[0].startswith("info/")]
    assert {r[2] for r in info_tags} == set(range(10))  # all 10 global steps


def test_linear_and_ce_tb_streams_bitwise_equal(tmp_path, tiny_drivers):
    _, linear_driver, ce_driver = tiny_drivers
    from simclr_pytorch_distributed_tpu import config as config_lib

    def go_linear(mode):
        cfg = config_lib.LinearConfig(
            model="resnet10", dataset="synthetic", batch_size=32, epochs=2,
            learning_rate=0.5, size=SIZE, val_batch_size=40,
            workdir=str(tmp_path / f"lin_{mode}"), print_freq=2, telemetry=mode,
        )
        linear_driver.run(config_lib.finalize_linear(cfg))

    def go_ce(mode):
        cfg = config_lib.LinearConfig(
            model="resnet10", dataset="synthetic", batch_size=32, epochs=2,
            learning_rate=0.1, size=SIZE, val_batch_size=40,
            workdir=str(tmp_path / f"ce_{mode}"), print_freq=2, telemetry=mode,
        )
        ce_driver.run(config_lib.finalize_linear(cfg, prefix="ce_"))

    lin = _tb_ab(go_linear)
    assert lin["sync"] == lin["async"] and lin["sync"]
    ce = _tb_ab(go_ce)
    assert ce["sync"] == ce["async"] and ce["sync"]
