"""device.idle_share.pretrain (%): layer "device", moves pretrain_imgs_per_s.

One minus the union of the device's operation intervals over the traced steady
stretch, on the chip that idles most. Source: device trace."""

import trace_reduce as tr


def read(run):
    if not run.get("stretches"):
        return None
    plane, (t0, t1, _) = run["planes"][run["worst"]], run["stretches"][run["worst"]]
    return 100.0 * (1.0 - tr.busy_seconds(plane, t0, t1) / ((t1 - t0) / 1e9))
