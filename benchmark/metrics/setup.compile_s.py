"""setup.compile_s (s): layer "set-up", moves setup_s.

Union of JAX's ``backend_compile`` spans before the window (track
``compile``): XLA's compile of a program, or its read from the persistent
compile cache (each span's ``cache_hit`` says which).

Source: the program's spans; ``setup_reduce`` has the split."""

import setup_reduce


def read(run):
    return setup_reduce.part(run, "compile")
