"""encoder.attn_core_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, under the scope ``attn_core`` of
the latent-attention layers (``named_scope`` inside
``encoder/block<k>/attn``): scores, causal mask, softmax and values, the
recomputed ones among them (``latent_scopes``). None where the step has no
such scope. Source: device trace."""

import latent_scopes as ls


def read(run):
    return ls.ms_per_step(run, ("attn_core",))
