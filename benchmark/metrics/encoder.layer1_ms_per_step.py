"""encoder.layer1_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, of the blocks of stage 1 (flax's
paths ``encoder/layer1_block<k>``): convolutions, batch norm, residual adds
and everything the compiler fused with them (``scope_reduce``).
Source: device trace."""

import scope_reduce as sr


def read(run):
    return sr.ms_per_step(run, ("layer1",))
