"""encoder.conv_roofline_share (%): layer "kernels", moves pretrain_imgs_per_s.

The least time one chip could take for one step's convolutions, the larger of
their operations over the peak rate and their least bytes over the peak
bandwidth (``conv_min_seconds`` of the file that the configuration names under
``flops``, from the configuration's shapes), over the time the trace gives them. Source: device trace."""

import trace_reduce as tr


def read(run):
    if not run.get("stretches"):
        return None
    measured = tr.per_step_max(run["planes"], run["stretches"], tr.is_kind("conv", run["kinds"]))
    if measured <= 0:
        return None
    rows = 2 * run["global_batch"] // run["chips"]
    least, _ = run["flops"].conv_min_seconds(
        run["config"]["model"], run["size"], rows,
        run["peaks"]["flops_per_s"], run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / measured
