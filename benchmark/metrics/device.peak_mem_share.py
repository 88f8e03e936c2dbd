"""device.peak_mem_share (%): layer "device", moves pretrain_imgs_per_s.

Peak bytes on the fullest chip over that chip's limit, read after the window
and before the reference runs, from the runtime's counter that holds the
program's temporaries (``peak_bytes_reserved`` where it is the larger).
Source: the runtime's memory counters."""


def read(run):
    mem = run["memory"]
    if not mem["bytes_limit"]:
        return None
    return 100.0 * mem["peak_bytes"] / mem["bytes_limit"]
