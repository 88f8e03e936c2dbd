"""step.unattributed_share (%): layer "whole step", moves pretrain_imgs_per_s.

Device seconds of the traced steady stretch whose operation names no bucket
(no ``op_name``, or a path under no scope), over the stretch's busy seconds:
how much of the step the per-scope metrics do not see (``scope_reduce``).
Source: device trace."""

import scope_reduce as sr


def read(run):
    got = sr.scope_seconds(run)
    if got is None or not got["busy_s"]:
        return None
    return 100.0 * sr.bucket_seconds(got["by_scope"], (sr.UNATTRIBUTED,)) / got["busy_s"]
