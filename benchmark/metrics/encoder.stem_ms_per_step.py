"""encoder.stem_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, of the stem: flax's paths
``encoder/conv1`` and ``encoder/bn1`` and what sits directly under
``encoder`` (the stem's ReLU and the 4x4 average pool after layer4), convolutions and everything fused with them (``scope_reduce``).
Source: device trace."""

import scope_reduce as sr


def read(run):
    return sr.ms_per_step(run, ("stem",))
