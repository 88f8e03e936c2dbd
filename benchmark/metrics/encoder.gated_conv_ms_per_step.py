"""encoder.gated_conv_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, under the scope ``gated_conv`` of
the gated short-convolution mixers (``named_scope`` inside ``conv_mixer``):
``B x``, the causal depthwise convolution, ``C u``, whatever the compiler
makes of them, the recomputed forward among them (``conv_scopes``). None
where the step has no such scope. Source: device trace."""

import conv_scopes as cs


def read(run):
    return cs.ms_per_step(run, ("gated_conv",))
