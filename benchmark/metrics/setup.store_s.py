"""setup.store_s (s): layer "set-up", moves setup_s.

The set-up span ``store`` around ``device_store.make_store``: placement and
the dataset's upload, less any trace, lowering or compile inside it.

Source: the program's spans; ``setup_reduce`` has the split."""

import setup_reduce


def read(run):
    return setup_reduce.part(run, "store")
