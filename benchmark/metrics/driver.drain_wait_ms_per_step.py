"""driver.drain_wait_ms_per_step (ms): layer "driver loop", moves
pretrain_imgs_per_s.

The ``drain_wait`` spans inside the window (``TelemetrySession.drain_global``
around ``executor.wait_idle()``: the main thread waiting, at an epoch's end
and at the window's, for the last flush and so for every step in flight),
summed, over the window's steps. Part of ``driver.host_phase_ms_per_step``:
subtract it there. Source: the program's spans."""

import scope_reduce as sr


def read(run):
    waits = [r["dur"] for r in sr.window_records(run)
             if r.get("ph") == "X" and r["name"] == "drain_wait"]
    return 1e3 * sum(waits) / run["window_steps"] if waits and run["window_steps"] else None
