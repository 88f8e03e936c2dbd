"""driver.dispatch_floor_ms (ms): layer "driver loop", moves pretrain_imgs_per_s.

The least ``dispatch_min_s`` of the window's ``flush_boundary`` spans: what
one ``update_fn(...)`` call costs the host when nothing blocks, i.e. the step
time below which the host sets the pace. Source: the program's counters."""

import scope_reduce as sr


def read(run):
    least = [r["args"]["dispatch_min_s"] for r in sr.window_records(run)
             if r["name"] == "flush_boundary" and "dispatch_min_s" in r.get("args", {})]
    return 1e3 * min(least) if least else None
