"""delta.decay_mean (ratio): layer "encoder", moves pretrain_imgs_per_s.

The mean per-token decay of the Gated DeltaNet layers' states, exp(g) over
tokens, value heads and layers: the ring column ``delta_decay_mean`` as the
newest ``health_window`` event inside the measured window has it (near 0:
each token's state forgets what came before it; near 1: it keeps it). None
where no such column is recorded. Source: program counter."""

import token_scopes as ts


def read(run):
    return ts.last_health_window(run, "delta_decay_mean")
