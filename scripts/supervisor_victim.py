#!/usr/bin/env python
"""A tiny REAL pretrain run with injectable faults — the supervisor's
scenario-matrix victim (scripts/supervisor_matrix.py, tests/test_supervise.py,
tests/test_fault_injection.py).

Same philosophy as tests/fault_injection_child.py: the only honest way to
prove the supervisor is to let it babysit the REAL driver in a real OS
process — real exit codes, real /metrics sidecar, real watchdog dumps, real
checkpoints. This wrapper shrinks the synthetic dataset to seconds per run
and adds three injectable faults, each gated by a one-shot marker file so
the supervisor's RELAUNCH of the same command runs clean (the transient-
failure shape the supervisor exists to absorb):

- ``--fault stall``: at the Nth flush-boundary preemption check the main
  thread writes the marker and sleeps forever — the flush boundary stops
  advancing, ``train_last_boundary_age_seconds`` climbs, the in-child
  watchdog (``--watchdog_secs``) dumps stacks, and the supervisor must
  kill (SIGTERM is absorbed by the preempt handler's flag — exactly how a
  wedged collective behaves — so the grace window lapses into SIGKILL);
- ``--fault nan``: the Nth finite-loss check raises NonFiniteLossError —
  the driver saves ``crash_epoch_N`` and exits with typed code 1;
- ``--fault collapse``: the health thresholds are made impossible
  (``eff_rank_min=1e9``), so the first health window alarms and
  ``--health_policy abort`` exits with typed code 3 (no marker: collapse
  is not transient, and the supervisor must GIVE UP, not relaunch);
- ``--straggler_ms`` (orthogonal to ``--fault``, own ``--straggler_marker``
  one-shot gate): paces every flush boundary by that much and publishes
  the fleet-skew gauges a 2-host fleet with a host this slow would expose
  (a single-process victim has no peers — utils/telemetry.py publishes
  zero skew — so the injection simulates the fleet view; the REAL gloo
  skew path is the matrix's 2-process straggler scenario). This is the
  uniform straggler fault the matrix drives next to stall/nan/collapse,
  and it composes with them for the chaos scenario.

Accepts main_supcon-style flags (``--resume`` included), so the
supervisor's appended ``--resume <run_dir>`` lands exactly as it would on
the real trainer. Prints ``SAVE_FOLDER <path>`` and ``DONE step=<n>`` like
the fault-injection child.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser("supervisor scenario victim")
    p.add_argument("--workdir", required=True)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--trial", default="victim")
    p.add_argument("--resume", default="")
    p.add_argument("--save_freq", type=int, default=1)
    p.add_argument("--metrics_port", type=int, default=0)
    p.add_argument("--watchdog_secs", type=float, default=0.0)
    p.add_argument("--health_freq", type=int, default=0)
    p.add_argument("--health_policy", default="warn")
    p.add_argument("--fault", default="none",
                   choices=["none", "stall", "nan", "collapse"])
    p.add_argument("--fault_step", type=int, default=3,
                   help="inject at the Nth call of the hooked check")
    p.add_argument("--fault_marker", default="",
                   help="one-shot gate: fault fires only while this file "
                        "is absent (it is created at injection time)")
    p.add_argument("--straggler_ms", type=float, default=0.0,
                   help="make THIS process a straggler: sleep this long at "
                        "every flush-boundary failure-code allgather and "
                        "publish the matching fleet-skew gauges (a "
                        "single-process victim has no peers, so "
                        "utils/telemetry.py publishes zero skew — the "
                        "injection simulates the 2-host fleet whose host "
                        "1 is this slow; the REAL multi-process skew "
                        "path is proven by the gloo straggler scenario). "
                        "Composable with --fault: the chaos scenario "
                        "drives straggler + collapse in one run")
    p.add_argument("--straggler_marker", default="",
                   help="one-shot gate for --straggler_ms (separate from "
                        "--fault_marker so the combination stays "
                        "independent): skew fires only while this file "
                        "is absent; created at the first injected "
                        "boundary, so the supervisor's relaunch runs "
                        "clean — the rebalanced-away shape")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    # the persistent compile cache is placed by JAX_COMPILATION_CACHE_DIR
    # (jax reads the variable itself; the parent sets it)

    import logging

    logging.basicConfig(stream=sys.stdout, level=logging.INFO, force=True)

    from simclr_pytorch_distributed_tpu import config as config_lib
    from simclr_pytorch_distributed_tpu.data import cifar as cifar_lib
    from simclr_pytorch_distributed_tpu.utils import guard, preempt

    # 256 examples at size 8 -> 7 steps/epoch at batch 32 (the fault-child
    # geometry: seconds per run once the compile cache is warm)
    _orig_synth = cifar_lib.synthetic_dataset
    cifar_lib.synthetic_dataset = (
        lambda n=2048, num_classes=10, seed=0, size=32: _orig_synth(
            n=256, num_classes=num_classes, seed=seed, size=8
        )
    )

    armed = args.fault != "none" and not (
        args.fault_marker and os.path.exists(args.fault_marker)
    )

    def trip_marker():
        if args.fault_marker:
            with open(args.fault_marker, "w") as f:
                f.write(args.fault)

    from simclr_pytorch_distributed_tpu.train import supcon as supcon_driver

    if armed and args.fault == "stall":
        calls = {"n": 0}
        real = preempt.requested_global

        def stalling_requested_global():
            calls["n"] += 1
            if calls["n"] == args.fault_step:
                trip_marker()
                print("FAULT stall: main thread wedged", flush=True)
                import time

                while True:  # survive the flag-setting SIGTERM handler,
                    time.sleep(3600)  # like a wedged collective would
            return real()

        # supcon's epoch loop reads the attribute through the module, so
        # one patch covers every call site
        preempt.requested_global = stalling_requested_global
    elif armed and args.fault == "nan":
        calls = {"n": 0}
        real_check = supcon_driver.check_finite_loss

        def poisoned_check(loss, step, enabled=True):
            calls["n"] += 1
            if calls["n"] == args.fault_step:
                trip_marker()
                print("FAULT nan: poisoning the loss check", flush=True)
                raise guard.NonFiniteLossError(float("nan"), step)
            return real_check(loss, step, enabled)

        supcon_driver.check_finite_loss = poisoned_check
    elif armed and args.fault == "collapse":
        # impossible bar: every healthy window "collapses"; under
        # --health_policy abort the run exits with typed code 3. Patch
        # the recipe-threshold resolver, not the HealthThresholds class:
        # RECIPE_HEALTH_THRESHOLDS holds prebuilt instances, so a class
        # patch never reaches the monitor for a known recipe (obs.py
        # imports the resolver at run setup, after this patch lands)
        real_thresholds = guard.HealthThresholds
        guard.thresholds_for_recipe = (
            lambda recipe: real_thresholds(eff_rank_min=1e9)
        )
        trip_marker()
        print("FAULT collapse: impossible health thresholds", flush=True)

    straggler_armed = args.straggler_ms > 0 and not (
        args.straggler_marker and os.path.exists(args.straggler_marker)
    )
    if straggler_armed:
        import time as _time

        from simclr_pytorch_distributed_tpu.utils import telemetry

        real_check = telemetry.TelemetrySession.check_failures_global
        skew_s = args.straggler_ms / 1e3

        def skewed_check(self, step_hint=0):
            # marker trips at the FIRST injected boundary (injection
            # time), so the relaunch of this same command runs clean
            if args.straggler_marker and not os.path.exists(
                args.straggler_marker
            ):
                with open(args.straggler_marker, "w") as f:
                    f.write(f"straggler {args.straggler_ms}ms")
                print("FAULT straggler: boundary skew armed", flush=True)
            _time.sleep(skew_s)  # genuinely pace the boundary
            real_check(self, step_hint)
            if self._gauges is not None:
                # what a 2-host fleet with host 1 this slow would publish
                # (utils/telemetry.py multi-process branch)
                self._gauges.set(
                    boundary_skew_seconds=skew_s,
                    boundary_straggler=1.0,
                    process_count=2.0,
                )

        telemetry.TelemetrySession.check_failures_global = skewed_check

    cfg = config_lib.SupConConfig(
        model="resnet10", dataset="synthetic", batch_size=32,
        epochs=args.epochs, learning_rate=0.05, temp=0.5, cosine=True,
        save_freq=args.save_freq, print_freq=1, size=8,
        workdir=args.workdir, seed=0, method="SimCLR", trial=args.trial,
        resume=args.resume, metrics_port=args.metrics_port,
        watchdog_secs=args.watchdog_secs, health_freq=args.health_freq,
        health_policy=args.health_policy,
    )
    cfg = config_lib.finalize_supcon(cfg)
    print(f"SAVE_FOLDER {cfg.save_folder}", flush=True)

    def run():
        state = supcon_driver.run(cfg)
        print(f"DONE step={int(state.step)}", flush=True)

    # the REAL typed-exit surface (utils/guard.py): NaN -> 1, collapse -> 3,
    # preempt -> 75 — what the supervisor classifies
    guard.exit_with_code(run)


if __name__ == "__main__":
    main()
