"""encoder.linear_attn_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, of the Gated DeltaNet layers
(``named_scope("linear_attn")`` inside flax's ``encoder/block<k>/attn``):
norm, projections, the causal convolution, gates, the chunked delta rule,
output norm and projection, and everything the compiler fused with them
(``delta_scopes``). None where the step has no such layer.
Source: device trace."""

import delta_scopes as ds


def read(run):
    return ds.ms_per_step(run, ds.LINEAR)
