"""simclr_pytorch_distributed_tpu — a TPU-native (JAX/XLA/pjit) framework with the
capabilities of Dyfine/SimCLR_pytorch_distributed.

The reference is a 2-GPU PyTorch DDP SimCLR/SupCon pretrainer (NCCL all-gather of
projection features + SyncBN) plus a single-GPU linear probe. This package rebuilds
it TPU-first:

- single-program SPMD over a ``jax.sharding.Mesh`` (GSPMD) instead of
  ``torch.distributed.launch`` + DDP (reference ``main_supcon.py:359-364``),
- cross-replica batch norm falls out of sharded-batch statistics instead of
  ``SyncBatchNorm.convert_sync_batchnorm`` (reference ``main_supcon.py:223-224``),
- the NT-Xent global-negatives gather is a differentiable logical-global matmul
  (XLA inserts the collectives) instead of ``torch.distributed.all_gather`` plus
  the local-tensor re-insertion trick (reference ``main_supcon.py:268-279``),
- augmentations run jitted on device instead of 8 PIL DataLoader workers
  (reference ``main_supcon.py:200-207``).
"""

import time

# The package's first line on the set-up clock: where the set-up span
# ``import`` starts (utils/tracing.imports_done). Stdlib only, so the bare
# package import stays free of jax and of ``utils``.
IMPORT_STARTED = time.monotonic()

__version__ = "0.1.0"


def __getattr__(name):
    # Lazy convenience re-export (PEP 562): the bare package import must
    # stay jax-free so the stdlib-ast invariant linter
    # (simclr_pytorch_distributed_tpu/analysis/, scripts/invariant_lint.py)
    # really runs on a box with no jax — an eager `from ops.losses import
    # supcon_loss` here pulled jax into every subpackage import.
    if name == "supcon_loss":
        from simclr_pytorch_distributed_tpu.ops.losses import supcon_loss

        return supcon_loss
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
