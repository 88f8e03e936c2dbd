"""encoder.attn_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, of the sparse-attention layers
(flax's paths ``encoder/block<k>/attn``) without their indexer: projections,
norms, rotary embedding, scores over the selected keys, softmax, values and
everything the compiler fused with them (``token_scopes``). None where the
step is no token encoder's. Source: device trace."""

import token_scopes as ts


def read(run):
    return ts.ms_per_step(run, ("attn",))
