"""aug.device_ms_per_step (ms): layer "augmentation", moves pretrain_imgs_per_s.

Device time a step of the operations whose ``op_name`` lies in the scope
``aug`` (``train/supcon.py``: ``two_crop_batch`` on the 2 x B views, and the
views' flattening in ``two_view_forward``), over the traced steady stretch on
the chip that idles most (``scope_reduce``). Source: device trace."""

import scope_reduce as sr


def read(run):
    return sr.ms_per_step(run, ("aug",))
