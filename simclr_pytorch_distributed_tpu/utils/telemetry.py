"""Zero-sync telemetry: the background flush pipeline behind the metric ring.

docs/PERF.md round 5 measured the last mapped driver overhead: every metric
flush is a synchronous D2H on the dispatch thread (~110 ms/window in round 5;
a real sync barrier on any host), costing ~5.5 ms/step at the
recipe's ``print_freq 20``. This module is the training-loop analogue of the
serve/ pipelined executor (PR 3's assembler/completer split): the main thread
SNAPSHOTS the device-side ring at each ``print_freq`` boundary and keeps
dispatching; the D2H, ``check_finite_loss``, meter math, TB writes, and the
progress log line run on one background telemetry thread, strictly FIFO.

Semantics contract (tested, not assumed — tests/test_telemetry.py):

- TB scalars: same tags, same steps, same float values as the synchronous
  path (jobs are FIFO on one thread; the values are the very same device
  computations, only fetched later);
- preemption: ``preempt.requested_global`` stays on the MAIN thread at the
  same deterministic flush boundaries — the collective decision never
  depended on the D2H completing;
- NaN detection: at most one window late, and COLLECTIVE. The worker's
  ``NonFiniteLossError`` re-raises on the main thread at the next boundary
  via :meth:`TelemetrySession.check_failures_global` (all hosts agree
  before any leaves the loop — async submission itself never raises, since
  flush completion timing is per-host) or at ``drain`` — under
  ``--nan_policy abort`` the run aborts one window later; under
  ``rollback`` the epoch is discarded from its boundary backup regardless,
  so the latency is invisible. Non-NaN flush failures (TB ``IOError`` etc.)
  exit as :class:`TelemetryFlushError` instead — never the NaN policy;
- epoch ends and emergency saves ``drain()`` first, so ``loss_avg``, the
  meters, and crash/preempt checkpoints see complete metrics (the same
  exception-forwarding discipline as ``EpochLoader``'s prefetch thread).

``mode='sync'`` runs every job inline on the calling thread — the control
arm for the A/B (scripts/flush_ab.py) and the reference-semantics fallback
(``--telemetry sync``). Failure handling is the SAME in both modes: job
exceptions are stored and surfaced through ``check_failures_global`` at the
boundary (a sync job raising straight out of ``submit`` would skip the
collective failure-code exchange and exit with the raw, unclassified type).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Optional, Sequence

from simclr_pytorch_distributed_tpu.ops.metrics import MetricRing
from simclr_pytorch_distributed_tpu.utils import tracing

_STOP = object()


class TelemetryFlushError(RuntimeError):
    """A background window-flush job failed for a reason OTHER than a
    non-finite loss (a TB write ``IOError``, a D2H fault, a bug in a consume
    callback). Deliberately distinct from ``NonFiniteLossError``: the NaN
    policy must not roll back epochs over an I/O error, so this aborts under
    BOTH ``--nan_policy`` modes. The original exception rides as
    ``__cause__`` on the host that saw it (under multi-host only the
    collective failure code crosses hosts)."""


class FlushExecutor:
    """One background worker draining window jobs FIFO; exceptions re-raise
    on the main thread at the next boundary."""

    def __init__(self, mode: str = "async"):
        if mode not in ("async", "sync"):
            raise ValueError(f"telemetry mode must be async|sync, got {mode!r}")
        self.mode = mode
        self._exc: Optional[BaseException] = None
        self._cv = threading.Condition()
        self._unfinished = 0
        self._closed = False
        if mode == "async":
            self._q: "queue.SimpleQueue" = queue.SimpleQueue()
            self._thread = threading.Thread(
                target=self._run, name="telemetry-flush", daemon=True
            )
            self._thread.start()

    # -- worker ----------------------------------------------------------
    def _run(self) -> None:
        while True:
            job = self._q.get()
            if job is _STOP:
                return
            try:
                # once poisoned, queued jobs are DISCARDED (their metrics
                # post-date the failure) until the main thread observes the
                # exception via poll(); poll clears the poison only after
                # the queue is drained, so no stale job can slip through.
                if self._exc is None:
                    job()
            except BaseException as e:  # noqa: BLE001 — forwarded, not handled
                self._exc = e
            finally:
                with self._cv:
                    self._unfinished -= 1
                    self._cv.notify_all()

    # -- main-thread API -------------------------------------------------
    def submit(self, job: Callable[[], None]) -> None:
        """Enqueue a window job; in ``sync`` mode it runs inline on the
        calling thread. Submission NEVER raises a job exception itself: whether a flush has completed by a given boundary
        is scheduling-dependent, so an eager raise here would surface on
        different hosts at different boundaries — failures surface through
        ``poll``/``drain``/``TelemetrySession.check_failures_global``, which
        the drivers call at deterministic points (queued jobs after a
        failure are discarded by the worker, so the queue stays bounded)."""
        if self._closed:
            # same lifecycle contract in BOTH modes — a submit-after-close
            # must not silently run under the sync control arm while the
            # async default raises
            raise RuntimeError("FlushExecutor is closed")
        if self.mode == "sync":
            # inline — the D2H stall stays on the caller, which is the whole
            # point of the control arm — but failures follow the SAME
            # deferred protocol as async: stored, then classified and raised
            # by the boundary's ``check_failures_global``/``poll``. A raw
            # raise here would leave the epoch loop BEFORE the failure-code
            # exchange, with the wrong type (a TB ``IOError`` instead of
            # ``TelemetryFlushError``) — the exact multi-host hazard
            # ``check_failures_global`` documents.
            if self._exc is None:
                try:
                    job()
                except BaseException as e:  # noqa: BLE001 — forwarded
                    self._exc = e
            return
        with self._cv:
            self._unfinished += 1
        self._q.put(job)

    def wait_idle(self) -> None:
        if self.mode == "sync":
            return
        with self._cv:
            while self._unfinished:
                self._cv.wait()

    def unfinished(self) -> int:
        """Window jobs submitted but not yet completed (the in-flight-windows
        gauge the trainer metrics sidecar exposes)."""
        with self._cv:
            return self._unfinished

    def poll(self) -> None:
        """Re-raise the first worker exception on the calling thread.

        Drains the queue first (the worker discards poisoned jobs), THEN
        clears the poison — so after the raise the executor is clean and
        reusable (the rollback policy keeps training on the same run).
        """
        if self._exc is None:
            return
        self.wait_idle()
        exc, self._exc = self._exc, None
        raise exc

    def drain(self) -> None:
        """Block until every submitted job completed; then surface errors.
        Call before reading meters and before emergency/epoch-end saves."""
        self.wait_idle()
        self.poll()

    def close(self) -> None:
        """Stop the worker. Never raises pending exceptions (it runs in
        ``finally`` blocks where a raise would mask the real failure)."""
        if self._closed:
            return
        self._closed = True
        if self.mode == "sync":
            return
        self._q.put(_STOP)
        self._thread.join()


class TelemetrySession:
    """The ring + executor pair the epoch drivers share.

    The driver's per-window flow is::

        ring_buf = session.init_buffer()                  # fresh each epoch
        state, ring_buf = update_fn(state, ring_buf, ...) # jitted write
        session.append(info, global_step)                 # host bookkeeping
        ...at each print_freq boundary...
        session.submit_window(ring_buf, consume)          # snapshot + queue

    ``submit_window`` snapshots the ring with a device-side copy (one tiny
    HBM->HBM program) BEFORE handing it to the executor: subsequent steps
    donate ``ring_buf``, so the flush must read a buffer donation can't
    reuse. Dispatch order guarantees the copy sees the window's writes.
    """

    def __init__(
        self,
        window: int,
        keys: Sequence[str],
        mode: str = "async",
        device_get: Optional[Callable] = None,
        watchdog=None,
        gauges=None,
    ):
        self.ring = MetricRing(window, keys, device_get=device_get)
        self.executor = FlushExecutor(mode)
        self.mode = mode
        self._window_start = time.time()
        # observability hooks (both host-only, both optional): the stall
        # watchdog is beaten and the sidecar gauges stamped at the same
        # deterministic flush boundaries the collective decisions use —
        # "the boundary stopped advancing" is exactly the signal that means
        # a wedged collective/device rather than ordinary slowness
        self._watchdog = watchdog
        self._gauges = gauges
        # this host's wait inside the PREVIOUS boundary's failure-code
        # allgather (ms), piggybacked on the next one (fleet skew; -1 =
        # nothing yet): see check_failures_global
        self._last_wait_ms = -1

    # ring pass-throughs used by the drivers
    def init_buffer(self, sharding=None):
        return self.ring.init_buffer(sharding)

    def pending_count(self) -> int:
        return self.ring.pending_count()

    def append(self, info, step: int) -> None:
        self.ring.append(info, step)

    def submit_window(self, ring_buf, consume: Callable) -> None:
        """Snapshot the pending window and hand ``consume(fetched_rows)`` to
        the executor. An empty window is a pure no-op — never a raise point:
        failures surface only through ``check_failures_global``/``drain``
        at the drivers' deterministic boundaries."""
        pending = self.ring.take_window()
        if not pending:
            return
        from simclr_pytorch_distributed_tpu.utils.checkpoint import jit_copy_tree

        snapshot = jit_copy_tree(ring_buf)

        def job():
            # the D2H + consume side of the window, on whichever thread the
            # executor runs it (its own track either way: under sync mode it
            # nests inside the main-thread boundary span, which must not
            # share a track with it — main:* tracks never nest)
            with tracing.span(
                "flush_job", track="telemetry:flush", steps=len(pending)
            ):
                consume(self.ring.resolve(snapshot, pending))

        self.executor.submit(job)

    def drain(self) -> None:
        self.executor.drain()

    def drain_global(self, step_hint: int = 0) -> None:
        """Collective drain for the epoch-loop exits.

        Blocks until every submitted job completed (no raise — completion
        timing is per-host), THEN observes failures collectively. Use ahead
        of COLLECTIVE operations (epoch-end and emergency checkpoint saves):
        a plain ``drain()`` raises host-locally, and a lone host skipping a
        collective save while its peers enter it deadlocks the job.
        Single-process this is ``drain()`` with the failure-type contract
        of :meth:`check_failures_global` applied."""
        # the wait is for the last window's D2H, and so for every step the
        # device still has in flight: at an epoch end it is where the main
        # thread spends about (steps in flight) x (step time), and where the
        # device's queue runs empty. Its own span on main:flush, around the
        # wait ONLY: check_failures_global opens main:collective, and main:*
        # tracks never nest.
        with tracing.span("drain_wait", track="main:flush", step=step_hint):
            self.executor.wait_idle()
        self.check_failures_global(step_hint)
        if self._watchdog is not None:
            # a completed drain is progress: the epoch-end save that often
            # follows must start with the full deadline
            self._watchdog.beat()

    def start_window_clock(self) -> None:
        """Reset the boundary-to-boundary wall clock (call at epoch start)."""
        self._window_start = time.time()
        if self._watchdog is not None:
            # an epoch edge is progress too: the first window of an epoch
            # must get the full deadline even after a long validation/save
            self._watchdog.beat()

    def flush_boundary(
        self,
        ring_buf,
        consume: Callable,
        batch_meter=None,
        step_hint: int = 0,
        dispatch=None,
    ) -> None:
        """The drivers' shared ``print_freq``-boundary protocol, in order:

        1. meter the closing window on the MAIN thread as
           boundary-to-boundary wall time / steps (``batch_meter``, when
           given): windows then partition the loop's wall clock exactly —
           a completion-timed measurement would double-count windows that
           overlap under async telemetry, and under ``sync`` the inline
           flush of window k lands in window k+1's delta (one-window
           shift, aggregate preserved);
        2. snapshot + queue the flush (ONE D2H per window, FIFO on the
           telemetry thread);
        3. observe failures COLLECTIVELY (``check_failures_global`` — the
           allgather schedules must match across hosts).

        The caller then makes its own collective preemption decision at the
        same boundary. The ordering is a multi-host correctness invariant:
        keep it here, not copied per driver. That decision
        (``preempt.requested_global``) is a SECOND single-int32 allgather
        right after this one — kept separate deliberately: folding the
        preempt flag into the failure code would couple this module to the
        signal handler's contract to save one tiny collective per
        ``print_freq`` window (single-process runs short-circuit both).

        When ``batch_meter`` is given, ``consume`` is called as
        ``consume(fetched, (val, avg))`` with the meter SNAPSHOTTED here on
        the main thread: the async job runs while later boundaries keep
        mutating the meter, so a worker-side read would print window k+1's
        (possibly torn) numbers against window k's log line.

        ``dispatch`` (``(sum, min, max)`` seconds, optional) is what the
        driver's hot loop accumulated around the closing window's update
        calls; it rides this boundary's span as ``dispatch_s`` /
        ``dispatch_min_s`` / ``dispatch_max_s`` so the hot loop itself
        records nothing.
        """
        attrs = {}
        if dispatch is not None:
            attrs = dict(zip(
                ("dispatch_s", "dispatch_min_s", "dispatch_max_s"),
                (round(d, 9) for d in dispatch),
            ))
        # span covers the main-thread boundary work (meter + snapshot +
        # queue) but NOT the collective failure observation below — that
        # records on its own main:collective track, and main:* phase tracks
        # must never nest across each other (the trace_report attribution
        # invariant, utils/tracing.py)
        with tracing.span(
            "flush_boundary", track="main:flush", step=step_hint,
            steps=self.pending_count(), **attrs,
        ):
            if batch_meter is not None:
                n_pending = self.pending_count()
                if n_pending:
                    now = time.time()
                    batch_meter.update(
                        (now - self._window_start) / n_pending, n=n_pending
                    )
                    self._window_start = now
                bt = (batch_meter.val, batch_meter.avg)
                self.submit_window(
                    ring_buf, lambda fetched: consume(fetched, bt)
                )
            else:
                self.submit_window(ring_buf, consume)
        self.check_failures_global(step_hint)
        # the boundary ADVANCED: beat the stall watchdog and stamp the
        # sidecar gauges (both host-only; no device sync, no transfer)
        if self._watchdog is not None:
            self._watchdog.beat()
        if self._gauges is not None:
            self._gauges.beat(step_hint)
            self._gauges.set(inflight_windows=self.executor.unfinished())

    def finish_epoch(self, submit_tail: Callable[[int], None], step_hint: int) -> None:
        """The drivers' shared epoch-end epilogue, ordering-critical like
        :meth:`flush_boundary` — keep it here, not copied per driver.

        ``submit_tail(step_hint)`` is the driver's own boundary helper,
        invoked for the final boundary: a no-op unless a short epoch left
        steps pending (the ring bookkeeping is session-lifetime — stale
        pending entries would poison the NEXT epoch's windows). Then a
        COLLECTIVE drain: meters are complete before the driver reads
        them, and the raise point stays matched across hosts ahead of the
        collective epoch-end/final save (a host-local raise here would
        skip a save its peers enter)."""
        submit_tail(step_hint)
        self.drain_global(step_hint)

    def close(self) -> None:
        self.executor.close()

    def _failure_code(self) -> int:
        """0 = clean, 1 = non-finite loss, 2 = any other flush failure,
        3 = representation-health abort (guard.HealthMonitor under
        ``--health_policy abort``)."""
        exc = self.executor._exc
        if exc is None:
            return 0
        from simclr_pytorch_distributed_tpu.utils.guard import (
            NonFiniteLossError,
            RepresentationHealthError,
        )

        if isinstance(exc, RepresentationHealthError):
            return 3
        return 1 if isinstance(exc, NonFiniteLossError) else 2

    def check_failures_global(self, step_hint: int = 0) -> None:
        """Collective failure observation for the epoch-loop boundary.

        Under async telemetry, WHETHER a host's flush (and therefore its
        ``check_finite_loss``) has completed by a given boundary is
        scheduling-dependent — so a lone host raising out of the epoch loop
        while its peers dispatch the next window's cross-host collectives
        would deadlock the job, exactly the hazard ``preempt.requested_global``
        guards on the preemption side. Every process calls this at every
        flush boundary (deterministic schedule); if ANY host has a pending
        worker failure, ALL hosts drain and raise at this same boundary —
        and they must leave through the SAME exception type, or the failure
        POLICY diverges across the job (host 0 rolling back while a peer
        aborts is a collective mismatch). The allgathered failure CODE picks
        that type deterministically, by max over hosts: a
        representation-health abort (code 3, ``--health_policy abort``)
        outranks everything — all three codes end the run, but the health
        verdict carries the actionable finding and is never subject to the
        NaN policy (rolling back a collapsed representation just re-detects
        it); a non-NaN flush failure (code 2: a TB-volume ``IOError``, a D2H
        fault) outranks a non-finite loss and exits as
        :class:`TelemetryFlushError` — it must NOT trigger the NaN policy,
        else ``--nan_policy rollback`` would discard clean epochs for a disk
        error; only a pure non-finite-loss window exits as
        ``NonFiniteLossError``. A host whose own windows were clean raises
        the type the code names (skew guard). Single-process jobs
        short-circuit to the local code — no collective in the hot loop.
        """
        import jax

        code = self._failure_code()
        if jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils

            # The allgather payload carries TWO int32s per host: the
            # failure code, plus this host's wait (ms) inside the PREVIOUS
            # boundary's allgather — widening an EXISTING collective, not
            # adding one (the zero-sync discipline). Every host then knows
            # the whole fleet's last-boundary waits: for a synchronous
            # collective each host's wait ≈ (last arrival − its own
            # arrival) + network, so the spread max(wait) − min(wait) is
            # the fleet's ARRIVAL skew and the host that waited LEAST is
            # the straggler (it arrived last; everyone else was parked on
            # it). One boundary stale by construction — the NaN-detection
            # latency convention. The span's own ts/dur are this host's
            # arrival/wait for the offline fleet report.
            prev_wait = self._last_wait_ms
            t_arrive = time.monotonic()
            with tracing.span(
                "failure_code_allgather", track="main:collective",
                step=step_hint, local_code=code,
            ):
                gathered = multihost_utils.process_allgather(
                    np.asarray([code, prev_wait], np.int32)
                )
            wait_s = time.monotonic() - t_arrive
            self._last_wait_ms = min(int(round(wait_s * 1e3)), 2**31 - 1)
            gathered = np.asarray(gathered).reshape(-1, 2)
            code = int(gathered[:, 0].max())
            if self._gauges is not None:
                self._gauges.set(collective_wait_seconds=wait_s)
            waits = gathered[:, 1]
            if len(waits) > 1 and (waits >= 0).all():
                skew_s = float(waits.max() - waits.min()) / 1e3
                if self._gauges is not None:
                    # skew + the straggler's IDENTITY and the fleet size:
                    # the supervisor's rebalance/exclude ladder needs to
                    # know WHO is slow and what share it holds, not just
                    # that someone is (supervise/observe.StragglerTracker)
                    self._gauges.set(
                        boundary_skew_seconds=skew_s,
                        boundary_straggler=float(waits.argmin()),
                        process_count=float(len(waits)),
                    )
                tracing.event(
                    "boundary_skew", track=tracing.FLEET_TRACK,
                    step=step_hint, skew_s=round(skew_s, 6),
                    straggler=int(waits.argmin()),
                )
        elif self._gauges is not None:
            # single process: no peers to wait on — publish the keys so a
            # scraper's dashboard reads 0, not absent (straggler identity
            # -1 = nobody: the supervisor's tracker treats a one-process
            # "fleet" as always benign)
            self._gauges.set(
                collective_wait_seconds=0.0, boundary_skew_seconds=0.0,
                boundary_straggler=-1.0, process_count=1.0,
            )
        # the matched instant every process just left (or, single-process,
        # a plain deterministic stamp): the fleet report's alignment ruler
        tracing.clock_anchor("flush_boundary", step=step_hint)
        if code == 0:
            return
        # the recorder is exactly for this moment: a post-mortem must show
        # WHICH boundary observed the failure and with what collective code
        tracing.event(
            "flush_failure", track="main:guard", code=code, step=step_hint
        )
        from simclr_pytorch_distributed_tpu.utils.guard import (
            NonFiniteLossError,
            RepresentationHealthError,
        )

        try:
            self.drain()  # re-raises this host's own exception when present
        except BaseException as e:
            # The exit TYPE must be a pure function of the ALLGATHERED code:
            # drain() can surface a failure that landed AFTER the code
            # exchange (this host's window was still in flight at the
            # snapshot), and classifying that locally would diverge the
            # policy across hosts — e.g. a late TB IOError aborting here
            # while the NaN peers roll back and re-enter the epoch loop's
            # collectives without us.
            if code == 3:
                if isinstance(e, RepresentationHealthError):
                    raise
                raise RepresentationHealthError(
                    ["peer reported a representation health alarm"], step_hint
                ) from e
            if code == 2:
                raise TelemetryFlushError(
                    f"telemetry flush failed near global step {step_hint}"
                ) from e
            # code == 1: every host exits through the NaN policy. A late
            # local non-NaN failure rides along as the chained cause (the
            # epoch is lost either way; if it recurs it allgathers as
            # code 2 at the next boundary and aborts collectively).
            if isinstance(e, NonFiniteLossError):
                raise
            raise NonFiniteLossError(float("nan"), step_hint) from e
        # skew guard: this host's own windows were clean but a peer flagged
        if code == 3:
            raise RepresentationHealthError(
                ["peer reported a representation health alarm"], step_hint
            )
        if code == 2:
            raise TelemetryFlushError(
                f"peer telemetry flush failed near global step {step_hint}"
            )
        raise NonFiniteLossError(float("nan"), step_hint)
