"""encoder.conv_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time of the convolutions and of the fusions the compiler built around
them (an operation of the trace is one of these when the compiled step's own
text says so: ``trace_reduce.hlo_kinds``), per step of the traced stretch, on
the chip where it is largest. Source: device trace."""

import trace_reduce as tr


def read(run):
    if not run.get("stretches"):
        return None
    t = tr.per_step_max(run["planes"], run["stretches"], tr.is_kind("conv", run["kinds"]))
    return 1e3 * t if t > 0 else None
