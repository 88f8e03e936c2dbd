"""encoder.attn_core_roofline_share (%): layer "kernels", moves pretrain_imgs_per_s.

The least time one chip could take for one step's attention scores and
values (the causal pairs of every row, in every head a query-key product of
the full head width and a value product; forward and backward, nothing
recomputed), the larger of their operations over the peak rate and their
least bytes over the peak bandwidth (``attn_core_min_seconds`` of the file
that the configuration names under ``flops``), over the device time under the
scope ``attn_core`` (``latent_scopes``), which holds the recomputed blocks
too. It counts the same work whatever implements it. None where the step has
no such scope. Source: device trace."""

import latent_scopes as ls


def read(run):
    measured_ms = ls.ms_per_step(run, ("attn_core",))
    if not measured_ms:
        return None
    rows = 2 * run["global_batch"] // run["chips"]
    least, _ = run["flops"].attn_core_min_seconds(
        run["config"]["model"], run["size"], rows,
        run["peaks"]["flops_per_s"], run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (measured_ms / 1e3)
