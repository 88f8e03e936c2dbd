"""encoder.conv_mixer_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, of the gated short-convolution
mixers whole (``named_scope`` ``conv_mixer`` under flax's
``encoder/block<k>/attn``, and ``gated_conv`` inside it): norm, ``W_bcx``,
the gates and the convolution, ``W_o``, the recomputed forward among them
(``conv_scopes``). None where the step has no such scope.
Source: device trace."""

import conv_scopes as cs


def read(run):
    return cs.ms_per_step(run, cs.CONV)
