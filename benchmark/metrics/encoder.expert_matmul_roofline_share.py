"""encoder.expert_matmul_roofline_share (%): layer "kernels", moves pretrain_imgs_per_s.

The least time one chip could take for one step's grouped expert products
(gate, up, down over the assignments that land on the held experts when the
load is balanced; forward and backward, nothing recomputed), the larger of
their operations over the peak rate and their least bytes over the peak
bandwidth (``expert_matmul_min_seconds`` of the file that the configuration
names under ``flops``), over the device time under the scope ``experts``
(``token_scopes``), which holds the recomputed products too. None where the
step has no such scope. Source: device trace."""

import token_scopes as ts


def read(run):
    measured_ms = ts.ms_per_step(run, ("experts",))
    if not measured_ms:
        return None
    least_of = run["flops"].expert_matmul_min_seconds
    rows = 2 * run["global_batch"] // run["chips"]
    least, _ = least_of(run["config"]["model"], run["size"], rows,
                        run["peaks"]["flops_per_s"], run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (measured_ms / 1e3)
