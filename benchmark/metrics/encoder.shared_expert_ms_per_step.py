"""encoder.shared_expert_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, under the scope ``shared`` of the
expert layers (``named_scope`` inside ``encoder/block<k>/moe``): the shared
experts' gated MLP over every token (``latent_scopes``). None where the step
has no such scope. Source: device trace."""

import latent_scopes as ls


def read(run):
    return ls.ms_per_step(run, ("shared",))
