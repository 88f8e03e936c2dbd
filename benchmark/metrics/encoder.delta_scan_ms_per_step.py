"""encoder.delta_scan_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step, forward and backward, under the scope ``delta_scan`` of
the Gated DeltaNet layers (``named_scope`` inside ``linear_attn``): the
chunked delta rule, its chunks' products, the inverse of each chunk's
triangular system and the recurrence over the chunks, the recomputed ones
among them (``delta_scopes``). None where the step has no such scope.
Source: device trace."""

import delta_scopes as ds


def read(run):
    return ds.ms_per_step(run, ("delta_scan",))
