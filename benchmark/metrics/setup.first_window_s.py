"""setup.first_window_s (s): layer "set-up", moves setup_s.

From the end of the ``first_step`` span (track ``main:compile``) to the
window's start, less trace, lowering and compile inside it: the first flush
window's other steps, its drain and the epoch-top copy.

Source: the program's spans; ``setup_reduce`` has the split."""

import setup_reduce


def read(run):
    return setup_reduce.part(run, "first_window")
