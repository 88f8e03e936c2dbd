"""moe.held_share (%): layer "encoder", moves pretrain_imgs_per_s.

The share of all assignments (tokens times experts a token) that land on the
experts this chip holds, averaged over the layers: the ring column
``moe_held_share`` as the newest ``health_window`` event inside the measured
window has it (held / all experts when balanced: 12.5%). None where no such
column is recorded. Source: program counter."""

import token_scopes as ts


def read(run):
    value = ts.last_health_window(run, "moe_held_share")
    return None if value is None else 100.0 * value
