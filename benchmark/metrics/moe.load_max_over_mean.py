"""moe.load_max_over_mean (ratio): layer "encoder", moves pretrain_imgs_per_s.

The busiest expert's share of the assignments over the mean share, over all
of the router's experts, averaged over the layers: the ring column
``moe_load_max_over_mean`` as the newest ``health_window`` event inside the
measured window has it (1 when balanced; the held experts' rows, and so the
grouped products' time, grow with it). None where no such column is recorded.
Source: program counter."""

import token_scopes as ts


def read(run):
    return ts.last_health_window(run, "moe_load_max_over_mean")
