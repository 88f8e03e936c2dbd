"""setup.boot_s (s): layer "set-up", moves setup_s.

From the process's start (``process_start``, which the program reads from
``/proc/self/stat``) to the package's first line (``package_import``): the
interpreter, ``import jax`` and the harness's ``require_tpu``, which starts
the TPU.

Source: the program's spans; ``setup_reduce`` has the split."""

import setup_reduce


def read(run):
    return setup_reduce.part(run, "boot")
