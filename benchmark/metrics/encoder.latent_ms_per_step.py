"""encoder.latent_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, under the scope ``latent`` of the
latent-attention layers (``named_scope`` inside ``encoder/block<k>/attn``):
the projection to the latent and the rotary key (``W_kva``), the latent's
norm, its expansion to every head's keys and values (``W_kvb``), the rotary
key's turn and its broadcast over the heads (``latent_scopes``). None where
the step has no such scope. Source: device trace."""

import latent_scopes as ls


def read(run):
    return ls.ms_per_step(run, ("latent",))
