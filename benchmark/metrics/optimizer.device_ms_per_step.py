"""optimizer.device_ms_per_step (ms): layer "optimizer", moves pretrain_imgs_per_s.

Device time a step of the scope ``optimizer`` (``train/supcon_step.py``:
``tx.update`` with the schedule, weight decay and momentum, and
``apply_updates``) (``scope_reduce``). Source: device trace."""

import scope_reduce as sr


def read(run):
    return sr.ms_per_step(run, ("optimizer",))
