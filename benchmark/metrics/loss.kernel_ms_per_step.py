"""loss.kernel_ms_per_step (ms): layer "loss", moves pretrain_imgs_per_s.

Device time of the fused NT-Xent kernel's custom calls, forward and backward,
per step of the traced stretch, on the chip where it is largest: the Mosaic
custom calls of the compiled step, of which the configurations have no other
(their convolutions resolve to XLA). A run whose loss resolved to another
implementation has no such call and reports nothing.
Source: device trace."""

import trace_reduce as tr


def read(run):
    if not run.get("stretches"):
        return None
    t = tr.per_step_max(run["planes"], run["stretches"], tr.is_kind("pallas", run["kinds"]))
    return 1e3 * t if t > 0 else None
