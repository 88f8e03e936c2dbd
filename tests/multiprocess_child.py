"""Child worker for test_multiprocess.py: one REAL OS process of a
multi-process data-parallel training step (the multi-host path of
parallel/mesh.py + data/pipeline.py).

Usage: python multiprocess_child.py <process_id> <num_processes> <port> [mode]

With num_processes > 1 it joins a gloo-backed jax.distributed cluster (each
process contributing CHILD_LOCAL_DEVICES virtual CPU devices — default 1, the
original one-device-per-process topology; 2 models a real pod host with
multiple local chips, where host-batch slicing, ring ppermute, and collective
saves cross BOTH the process and the local-device boundary) and prints the
first training step's loss; with num_processes == 1 it computes the same
GLOBAL step alone (CHILD_LOCAL_DEVICES devices, default 2) as the reference
value. The parent asserts all printed losses match.

mode 'driver' runs the FULL pretrain driver (supcon.run) instead of one step:
epoch loops, meters, process-0-gated checkpointing/logging — the closest this
host can get to a real 2-host launch.
"""

import os
import sys

pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
mode = sys.argv[4] if len(sys.argv) > 4 else "step"
# devices this process contributes; the single-process reference defaults to
# 2 so it reproduces the same global partitioning as 2 x 1-device processes
ndev_local = int(os.environ.get("CHILD_LOCAL_DEVICES", "2" if nproc == 1 else "1"))
if ndev_local > 1:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev_local}"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
if nproc > 1:
    # cross-process CPU collectives need the gloo implementation selected
    # BEFORE the backend is created — without it every multi-process jit
    # dies with "Multiprocess computations aren't implemented on the CPU
    # backend" (the env-var spelling does not reach this flag on this
    # jax/jaxlib, so it must be a config update here)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
# the persistent compile cache is placed by JAX_COMPILATION_CACHE_DIR
# (jax reads the variable itself; the parent sets it)
if nproc > 1:
    jax.distributed.initialize(
        coordinator_address=f"localhost:{port}",
        num_processes=nproc,
        process_id=pid,
    )

if mode in ("driver", "driver_partial", "ce"):
    # full drivers: tiny synthetic run; process 0 owns I/O
    from simclr_pytorch_distributed_tpu import config as config_lib
    from simclr_pytorch_distributed_tpu.data import cifar as cifar_lib

    _orig = cifar_lib.synthetic_dataset
    cifar_lib.synthetic_dataset = (
        lambda n=2048, num_classes=10, seed=0, size=32: _orig(
            n=128, num_classes=num_classes, seed=seed, size=8
        )
    )
    workdir = sys.argv[5]

    if mode == "ce":
        # the CE driver shares broadcast_from_main/collective-save machinery
        # that only supcon exercised before (round-2 weak #5)
        from simclr_pytorch_distributed_tpu.train import ce as ce_driver

        cfg = config_lib.LinearConfig(
            model="resnet10", dataset="synthetic", batch_size=32, epochs=2,
            learning_rate=0.05, save_freq=2, print_freq=2, size=8,
            workdir=workdir, seed=0, trial="mpce",
        )
        cfg = config_lib.finalize_linear(cfg, prefix="ce_")
        best_acc, _ = ce_driver.run(cfg)
        print(f"CE best_acc={best_acc:.4f} save_folder={cfg.save_folder}",
              flush=True)
        sys.exit(0)

    from simclr_pytorch_distributed_tpu.train import supcon as supcon_driver

    # fleet-evidence hook (docs/evidence/fleet_report_r13.json): make ONE
    # process a deliberate straggler by delaying its arrival at every
    # flush-boundary failure-code allgather — the injected skew must show
    # up in trace_report --fleet's skew table with this process named
    straggler_ms = float(os.environ.get("FLEET_STRAGGLER_MS", "0") or 0)
    if straggler_ms and pid == int(os.environ.get("FLEET_STRAGGLER_PID", "1")):
        import time as _time

        from simclr_pytorch_distributed_tpu.utils.telemetry import (
            TelemetrySession,
        )

        _orig_check = TelemetrySession.check_failures_global

        def _late_check(self, step_hint=0):
            _time.sleep(straggler_ms / 1e3)
            return _orig_check(self, step_hint)

        TelemetrySession.check_failures_global = _late_check

    epochs = int(sys.argv[6]) if len(sys.argv) > 6 else 2
    resume = sys.argv[7] if len(sys.argv) > 7 else ""
    cfg = config_lib.SupConConfig(
        model="resnet10", dataset="synthetic", batch_size=32, epochs=epochs,
        learning_rate=0.05, temp=0.5, cosine=True, syncBN=True,
        save_freq=2, print_freq=2, size=8, workdir=workdir, seed=0,
        method="SimCLR", trial="mp", resume=resume,
        # supervised-fleet hook (scripts/fleet_launcher.py sets the env on
        # process 0 only): expose the /metrics sidecar so the supervisor
        # scrapes the REAL gloo fleet's skew gauges
        metrics_port=int(os.environ.get("CHILD_METRICS_PORT", "0") or 0),
    )
    cfg = config_lib.finalize_supcon(cfg)

    if mode == "driver_partial":
        # simulated mid-job crash: die at the START of epoch 3, after the
        # (async) epoch-2 scheduled save; run()'s finally drains the save
        _orig_epoch = supcon_driver.train_one_epoch

        def _patched(epoch, *a, **k):
            if epoch == 3:
                raise RuntimeError("simulated crash before epoch 3")
            return _orig_epoch(epoch, *a, **k)

        supcon_driver.train_one_epoch = _patched
        try:
            supcon_driver.run(cfg)
            raise SystemExit("expected the simulated crash")
        except RuntimeError:
            print(f"PARTIAL save_folder={cfg.save_folder}", flush=True)
            sys.exit(0)

    def _run_and_print():
        state = supcon_driver.run(cfg)
        import jax as _jax

        digest = sum(
            float(abs(x).sum()) for x in _jax.tree.leaves(state.params)
        )
        print(
            f"DRIVER step={int(state.step)} digest={digest:.6f} "
            f"save_folder={cfg.save_folder}",
            flush=True,
        )

    if os.environ.get("CHILD_GUARDED"):
        # supervised-fleet hook: run under the drivers' typed exit-code
        # surface so a collective preemption leaves as the clean exit 75
        # the supervisor's preempt contract classifies (without it a
        # PreemptionError would crash out as a generic rc 1)
        from simclr_pytorch_distributed_tpu.utils import guard as guard_lib

        guard_lib.exit_with_code(_run_and_print)
    else:
        _run_and_print()
    sys.exit(0)

import jax.numpy as jnp
import numpy as np

from simclr_pytorch_distributed_tpu.data.pipeline import EpochLoader
from simclr_pytorch_distributed_tpu.models import SupConResNet
from simclr_pytorch_distributed_tpu.ops.schedules import make_lr_schedule
from simclr_pytorch_distributed_tpu.parallel.mesh import (
    create_mesh,
    shard_host_batch,
)
from simclr_pytorch_distributed_tpu.train.state import (
    create_train_state,
    make_optimizer,
)
from simclr_pytorch_distributed_tpu.train.supcon_step import (
    SupConStepConfig,
    make_sharded_train_step,
)

# mode 'fused'/'fused_supcon' needs >= 8 anchor rows per device (the sharded
# kernel's tiling floor, ops/pallas_loss.py _pick_block): global batch 16 ->
# 32 view rows -> m=8 on the 4-device topologies.
B = 16 if mode.startswith("fused") else 8
size = 8
model = SupConResNet(model_name="resnet10")
schedule = make_lr_schedule(
    learning_rate=0.05, epochs=2, steps_per_epoch=2, cosine=True
)
tx = make_optimizer(schedule, momentum=0.9, weight_decay=1e-4)
state = create_train_state(model, tx, jax.random.key(0), jnp.zeros((2, size, size, 3)))
cfg = SupConStepConfig(
    # 'fused_supcon' drives the label-carrying (SupCon) leg of the sharded
    # fused kernel; every other mode keeps the SimCLR recipe
    method=("SupCon" if mode == "fused_supcon" else "SimCLR"),
    temperature=0.5, epochs=2, steps_per_epoch=2, grad_div=2.0,
    # mode 'ring': the ppermute-rotating sharded loss across REAL process
    # boundaries — the DP step only exercises psum/all-gather over gloo.
    # mode 'fused'/'fused_supcon': the shard_map-sharded Pallas kernel
    # (interpret mode on CPU), the exact path resolve_loss_impl('auto')
    # selects on multi-device TPU meshes — its check_vma=False/psum-cotangent
    # custom VJP is the plumbing most at risk across process boundaries.
    loss_impl={"ring": "ring", "fused": "fused", "fused_supcon": "fused"}.get(
        mode, "dense"
    ),
)
mesh = create_mesh()
assert mesh.size == nproc * ndev_local, (mesh, nproc, ndev_local)
step = make_sharded_train_step(
    model, tx, schedule, cfg, mesh, state_shape=state, donate=False
)

# identical dataset on every process; EpochLoader slices this process's
# contiguous block of each global batch (the DistributedSampler equivalent)
rng = np.random.default_rng(0)
images = rng.standard_normal((2 * B, 2, size, size, 3)).astype(np.float32)
labels = rng.integers(0, 4, 2 * B).astype(np.int32)
loader = EpochLoader(
    images, labels, B, base_seed=0,
    process_index=jax.process_index(), process_count=jax.process_count(),
    prefetch=0,
)
# TWO steps: step 2's loss depends on step 1's parameter update, so the
# printed value witnesses the BACKWARD (grad + optimizer + collectives)
# across the process boundary, not just the forward loss reduction.
for imgs_local, labs_local in loader.epoch(1):
    batch = shard_host_batch((imgs_local, labs_local), mesh)
    state, metrics = step(state, batch[0], batch[1])
print(f"LOSS {float(metrics['loss']):.8f}", flush=True)
