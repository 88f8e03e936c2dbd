"""setup.trace_lower_s (s): layer "set-up", moves setup_s.

Union of JAX's ``trace`` and ``lower`` spans of every program before the
window (track ``compile``: Python to jaxpr, jaxpr to the compiler's module),
less any backend compile inside them. The persistent cache keeps only
executables, so a warm run pays this in full.

Source: the program's spans; ``setup_reduce`` has the split."""

import setup_reduce


def read(run):
    return setup_reduce.part(run, "trace_lower")
