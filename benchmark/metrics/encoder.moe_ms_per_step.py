"""encoder.moe_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, of the expert layers (flax's paths
``encoder/block<k>/moe``): norm, router, sorting the held assignments, the
gather and scatter of token rows, and the grouped products under ``experts``
(``token_scopes``). None where the step is no token encoder's.
Source: device trace."""

import token_scopes as ts


def read(run):
    return ts.ms_per_step(run, ("moe", "experts"))
