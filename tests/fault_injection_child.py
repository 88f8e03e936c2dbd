"""Child worker for tests/test_fault_injection.py: one REAL OS process
running the supcon pretrain driver on a tiny synthetic config, so the parent
can deliver actual signals (SIGTERM, SIGKILL) at randomized steps and then
resume — the only honest way to test the preemption machinery end-to-end
(an in-process simulation cannot witness exit codes or kill -9 torn state).

Usage: python fault_injection_child.py <workdir> <epochs> <resume> <trial> \
           [save_freq] [data_placement] [ngpu] [syncbn]

``ngpu``/``syncbn`` exist for the elastic-resume mesh matrix (the parent
also rewrites XLA_FLAGS' host-platform device count per child): pinning
``--ngpu`` to a constant and ``--syncBN`` on removes the two documented
shape-dependent terms (gradient divisor, per-device BN statistics), which
is exactly the configuration under which an N-device -> M-device resume
must reproduce the uninterrupted run (docs/RESILIENCE.md elastic-resume
contract).

Prints, on stdout (parent parses these):
- ``SAVE_FOLDER <path>``  once config is finalized (before training);
- the driver's ``Train: [e][s/S]`` log lines, one per step (print_freq=1);
- ``DONE step=<n>`` only when the run completes uninterrupted.

Exit codes: 0 done; preempt.EXIT_PREEMPTED (75) after a clean
SIGTERM-triggered emergency checkpoint; anything else is a real failure.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
# the persistent compile cache is placed by JAX_COMPILATION_CACHE_DIR
# (jax reads the variable itself; the parent sets it)

import logging  # noqa: E402

# the parent reads stdout; route the driver's log lines there unbuffered
logging.basicConfig(stream=sys.stdout, level=logging.INFO, force=True)

from simclr_pytorch_distributed_tpu import config as config_lib  # noqa: E402
from simclr_pytorch_distributed_tpu.data import cifar as cifar_lib  # noqa: E402

# 256 examples at size 8 -> 224 train -> 7 steps/epoch at batch 32: enough
# steps that a SIGTERM sent after the first step's log line is always
# observed MID-epoch (the handler runs during step 2's host code), small
# enough that a child run is seconds after the first compile is cached.
_orig_synthetic = cifar_lib.synthetic_dataset
cifar_lib.synthetic_dataset = (
    lambda n=2048, num_classes=10, seed=0, size=32: _orig_synthetic(
        n=256, num_classes=num_classes, seed=seed, size=8
    )
)

workdir = sys.argv[1]
epochs = int(sys.argv[2])
resume = sys.argv[3]
trial = sys.argv[4]
save_freq = int(sys.argv[5]) if len(sys.argv) > 5 else 100
# 'auto' resolves to DEVICE placement here (tiny in-RAM synthetic set on
# CPU); the parent pins 'host' to prove the preemption/resume contract on
# the per-step H2D loop too — it is placement-independent (RESILIENCE.md)
data_placement = sys.argv[6] if len(sys.argv) > 6 else "auto"
ngpu = sys.argv[7] if len(sys.argv) > 7 else "2"
sync_bn = (sys.argv[8] == "1") if len(sys.argv) > 8 else False

from simclr_pytorch_distributed_tpu.train import supcon as supcon_driver  # noqa: E402

cfg = config_lib.SupConConfig(
    model="resnet10", dataset="synthetic", batch_size=32, epochs=epochs,
    learning_rate=0.05, temp=0.5, cosine=True, save_freq=save_freq,
    print_freq=1, size=8, workdir=workdir, seed=0, method="SimCLR",
    trial=trial, resume=resume, data_placement=data_placement,
    ngpu=config_lib.ngpu_arg(ngpu), syncBN=sync_bn,
)
cfg = config_lib.finalize_supcon(cfg)
print(f"SAVE_FOLDER {cfg.save_folder}", flush=True)

state = supcon_driver.run(cfg)
print(f"DONE step={int(state.step)}", flush=True)
