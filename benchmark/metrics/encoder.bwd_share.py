"""encoder.bwd_share (%): layer "encoder", moves pretrain_imgs_per_s.

Of the device seconds of stem, the four stages and the head over the traced
steady stretch, the share whose ``op_name`` is under ``transpose(``: the
backward pass (``scope_reduce``). Source: device trace."""

import scope_reduce as sr


def read(run):
    got = sr.scope_seconds(run)
    if got is None:
        return None
    whole = sr.bucket_seconds(got["by_scope"], sr.ENCODER)
    return 100.0 * sr.bucket_seconds(got["by_scope"], sr.ENCODER, "bwd") / whole if whole else None
