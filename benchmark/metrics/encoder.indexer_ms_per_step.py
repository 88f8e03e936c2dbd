"""encoder.indexer_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, under the scope ``indexer`` of the
sparse-attention layers: the indexer's projections and scores (float32 at
highest precision), the top-k selection's bisection, and its KL
(``token_scopes``). None where the step is no token encoder's.
Source: device trace."""

import token_scopes as ts


def read(run):
    return ts.ms_per_step(run, ("indexer",))
