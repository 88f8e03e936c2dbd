"""moe.route_bias_max_abs (ratio): layer "encoder", moves pretrain_imgs_per_s.

The largest component of the routers' load-correcting bias, averaged over the
expert layers: the ring column ``route_bias_max_abs`` as the newest
``health_window`` event inside the measured window has it (0 at rest; each
step moves a component by the configuration's ``bias_update_rate``, so it
says how far the correction has moved the choice of experts, and with it the
held experts' rows). None where no such column is recorded.
Source: program counter."""

import token_scopes as ts


def read(run):
    return ts.last_health_window(run, "route_bias_max_abs")
