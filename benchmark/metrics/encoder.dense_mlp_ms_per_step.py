"""encoder.dense_mlp_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, of the leading dense layers
(flax's paths ``encoder/block<k>/mlp``): norm and gated MLP, a few rows at a
time (``latent_scopes``). None where the step has no such module.
Source: device trace."""

import latent_scopes as ls


def read(run):
    return ls.ms_per_step(run, ("mlp",))
