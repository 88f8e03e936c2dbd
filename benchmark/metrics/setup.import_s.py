"""setup.import_s (s): layer "set-up", moves setup_s.

The set-up span ``import``: from the package's first line to the program's
first ask of the backend (``is_main_process`` in the harness's flag
parsing) or the entry of ``train.supcon.enable_compile_cache``, whichever is
first: the package's imports and the configuration's modules.

Source: the program's spans; ``setup_reduce`` has the split."""

import setup_reduce


def read(run):
    return setup_reduce.part(run, "import")
