"""RunObservability — the epoch drivers' one-call observability wiring.

All three drivers want the identical stack (flight recorder + stall
watchdog + Prometheus sidecar) with the identical lifecycle, and the
teardown ORDER is a correctness property: the recorder must outlive the
last ``wait_for_saves()`` (so the final ``checkpoint_commit`` span lands
in the record) and the watchdog must still be watching while that drain
can wedge. Keeping the wiring here — the ``device_store.make_store``
convention — means the order cannot drift between drivers.

Usage (see train/supcon.py)::

    obs = RunObservability(cfg, name="supcon")
    telemetry = TelemetrySession(..., watchdog=obs.watchdog,
                                 gauges=obs.gauges)
    try:
        ...
    finally:
        ...
        wait_for_saves()   # BEFORE obs.close(): the commit span records
        obs.close()
"""

from __future__ import annotations

import logging

from simclr_pytorch_distributed_tpu.utils import prom, tracing
from simclr_pytorch_distributed_tpu.utils.checkpoint import pending_saves

logger = logging.getLogger(__name__)


class RunObservability:
    """Build (and later tear down, in the right order) the per-run
    observability stack from a trainer config:

    - ``recorder`` — installed as the module-level tracing recorder, which
      takes over the set-up records made before it (``process_start``, the
      ``import`` span, compiles), and fed JAX's ``trace`` / ``lower`` /
      ``backend_compile`` spans of every program, the last with
      ``cache_hit`` (``tracing.forward_compile_events``); ``None`` under
      ``--flight_recorder off``;
    - ``watchdog`` — a started :class:`tracing.StallWatchdog` beating on
      the flush boundary (via ``TelemetrySession``); ``None`` unless
      ``--watchdog_secs > 0``;
    - ``gauges`` + the ``--metrics_port`` sidecar server; ``None`` when
      the port is 0;
    - ``health`` — a :class:`guard.HealthMonitor` (the windowed
      collapse/divergence detector fed by the flush-boundary consume jobs)
      when the config carries health flags with ``health_freq > 0``
      (pretrain only); ``None`` otherwise.
    """

    def __init__(self, cfg, name: str):
        self.recorder = tracing.recorder_for_run(
            cfg.save_folder, enabled=(cfg.flight_recorder != "off")
        )
        tracing.install(self.recorder)
        if self.recorder is not None:
            # the run's compile record (track "compile"): listeners forward
            # to whichever recorder is installed when a compile happens
            tracing.forward_compile_events()
        self.watchdog = None
        if cfg.watchdog_secs > 0:
            self.watchdog = tracing.StallWatchdog(
                cfg.watchdog_secs, cfg.save_folder, recorder=self.recorder,
                name=name,
            )
        self.health = None
        if getattr(cfg, "health_freq", 0) > 0:
            from simclr_pytorch_distributed_tpu.utils.guard import (
                HealthMonitor,
                thresholds_for_recipe,
            )

            from simclr_pytorch_distributed_tpu.recipes import (
                recipe_metric_keys,
            )

            # per-recipe bars (guard.RECIPE_HEALTH_THRESHOLDS): the
            # negative-free recipes run under a raised eff-rank bar —
            # there the collapse detector is load-bearing. The recipe's
            # own metric columns ride the same window stream.
            self.health = HealthMonitor(
                policy=getattr(cfg, "health_policy", "warn"),
                thresholds=thresholds_for_recipe(
                    getattr(cfg, "recipe", None)
                ),
                extra_keys=recipe_metric_keys(
                    getattr(cfg, "recipe", None)
                ),
            )
        self.gauges = self.sidecar = None
        if cfg.metrics_port:
            self.gauges = prom.TrainerGauges()
            self.gauges.register("checkpoint_pending_saves", pending_saves)
            if self.recorder is not None:
                # records evicted from the recorder's bounded in-memory
                # ring (trace.json / watchdog snapshots truncated; the
                # jsonl keeps all) — a saturated recorder must be an
                # operator-visible signal, not a silent loss
                rec = self.recorder
                self.gauges.register(
                    "recorder_dropped_records", lambda: rec.dropped
                )
            self.sidecar = prom.start_metrics_server(
                cfg.metrics_port, self.gauges.prometheus_text,
                host=getattr(cfg, "metrics_host", "127.0.0.1"),
            )
            logger.info(
                "metrics sidecar on %s:%d",
                *self.sidecar.server_address[:2],
            )

    def set_epoch(self, epoch: int) -> None:
        if self.gauges is not None:
            self.gauges.set(epoch=epoch)

    def staged(self) -> None:
        """Call right after ``make_store`` returns. The stack is built
        BEFORE placement resolution (so the placement collective — a real
        deadlock candidate — runs under the armed watchdog and its span
        lands on the record), but the store's one-time dataset upload can
        be large: without this beat that staging time would eat into the
        first flush-boundary deadline, which ``--watchdog_secs`` is only
        documented to cover from compile onward (a spurious staging dump
        would be read by the supervisor as a stall)."""
        if self.watchdog is not None:
            self.watchdog.beat()

    def close(self, exit_code: int = None) -> None:
        """Teardown, last in the driver's ``finally`` (after the final
        ``wait_for_saves()``): stop the watchdog/sidecar threads, then
        uninstall and close the recorder — ``close()`` exports trace.json
        and never raises.

        ``exit_code`` (the drivers pass ``guard.exit_code_for`` of the
        in-flight exception) stamps the terminal ``train_exit_code`` gauge
        and records a final ``run_exit`` event before the sidecar stops —
        the supervisor's last scrape and the recorder's last line both
        classify the exit without log parsing."""
        if exit_code is not None:
            if self.gauges is not None:
                self.gauges.set_exit_code(exit_code)
            tracing.event("run_exit", track="main:guard", code=int(exit_code))
        if self.watchdog is not None:
            self.watchdog.close()
        if self.sidecar is not None:
            self.sidecar.shutdown()
        tracing.uninstall()
        if self.recorder is not None:
            self.recorder.close()
