"""encoder.delta_scan_roofline_share (%): layer "kernels", moves pretrain_imgs_per_s.

The least time one chip could take for one step's delta rule (the chunked
form's products at chunks of ``chunk_size`` tokens in every value head,
forward and backward, nothing recomputed; the least bytes: q, k, v, g, beta
in and o out, and their gradients), the larger of the operations over the
peak rate and the bytes over the peak bandwidth (``delta_scan_min_seconds``
of the file that the configuration names under ``flops``), over the device
time under the scope ``delta_scan`` (``delta_scopes``), which holds the
recomputed forward too. It counts the same work whatever implements it. None
where the step has no such scope. Source: device trace."""

import delta_scopes as ds


def read(run):
    measured_ms = ds.ms_per_step(run, ("delta_scan",))
    if not measured_ms:
        return None
    rows = 2 * run["global_batch"] // run["chips"]
    least, _ = run["flops"].delta_scan_min_seconds(
        run["config"]["model"], run["size"], rows,
        run["peaks"]["flops_per_s"], run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (measured_ms / 1e3)
