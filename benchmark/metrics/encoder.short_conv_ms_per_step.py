"""encoder.short_conv_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, under the scope ``short_conv`` of
the Gated DeltaNet layers (``named_scope`` inside ``linear_attn``): the
causal depthwise convolution and its ``silu``, whether XLA's fusions or the
Mosaic calls ``short_conv_fwd`` / ``short_conv_bwd`` make them, the
recomputed forward among them (``delta_scopes``). None where the step has no
such scope.
Source: device trace."""

import delta_scopes as ds


def read(run):
    return ds.ms_per_step(run, ("short_conv",))
