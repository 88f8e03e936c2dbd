"""loss.device_ms_per_step (ms): layer "loss", moves pretrain_imgs_per_s.

Device time a step of the scope ``loss`` (``train/supcon_step.py``: feature
norms, the normalize, the contrastive term with its custom-VJP backward, the
gradient scale): glue and kernel together, where ``loss.kernel_ms_per_step``
reads the kernel's custom calls alone (``scope_reduce``).
Source: device trace."""

import scope_reduce as sr


def read(run):
    return sr.ms_per_step(run, ("loss",))
