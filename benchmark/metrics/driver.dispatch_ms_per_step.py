"""driver.dispatch_ms_per_step (ms): layer "driver loop", moves
pretrain_imgs_per_s.

Host seconds inside the window's ``update_fn(...)`` calls
(``train_one_epoch`` accumulates two clock reads a step; each
``flush_boundary`` span carries its window's sum as ``dispatch_s``), over the
steps of those spans. Dispatch is asynchronous: past the enqueue cost this is
the host waiting for room in the device's queue. Source: the program's
counters."""

import scope_reduce as sr


def read(run):
    spans = [r["args"] for r in sr.window_records(run)
             if r["name"] == "flush_boundary" and "dispatch_s" in r.get("args", {})]
    steps = sum(a["steps"] for a in spans)
    return 1e3 * sum(a["dispatch_s"] for a in spans) / steps if steps else None
