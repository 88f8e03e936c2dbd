"""driver.host_phase_ms_per_step (ms): layer "driver loop", moves
pretrain_imgs_per_s.

The flight recorder's ``main:*`` spans that lie inside the window (flush
boundaries, the store's epoch gather, collective decisions; the epoch envelope
and the compile span left out), summed, over the window's steps. Host clock,
host-visible boundaries only. Source: the program's spans."""


def read(run):
    marks = {r["name"]: r["ts"] for r in run["records"] if r.get("track") == "bench"}
    if "bench_window_start" not in marks or "bench_window_end" not in marks:
        return None
    t0, t1 = marks["bench_window_start"], marks["bench_window_end"]
    spent = sum(
        r["dur"] for r in run["records"]
        if r.get("ph") == "X" and r["track"].startswith("main:")
        and r["track"] not in ("main:epoch", "main:compile") and t0 <= r["ts"] <= t1)
    return 1e3 * spent / run["window_steps"] if run["window_steps"] else None
