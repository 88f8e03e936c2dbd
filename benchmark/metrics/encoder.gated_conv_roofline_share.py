"""encoder.gated_conv_roofline_share (%): layer "kernels", moves pretrain_imgs_per_s.

The least time one chip could take for one step's gated convolutions' core
(``B x``, the taps, ``C u`` in every channel, forward and backward, nothing
recomputed; the least bytes: ``B``, ``C``, ``x`` and the taps in and ``y``
out, and their gradients), the larger of the operations over the peak rate
and the bytes over the peak bandwidth (``gated_conv_min_seconds`` of the file
that the configuration names under ``flops``), over the device time under the
scope ``gated_conv`` (``conv_scopes``), which holds the recomputed forward
too. It counts the same work whatever implements it. None where the step has
no such scope. Source: device trace."""

import conv_scopes as cs


def read(run):
    measured_ms = cs.ms_per_step(run, ("gated_conv",))
    if not measured_ms:
        return None
    rows = 2 * run["global_batch"] // run["chips"]
    least, _ = run["flops"].gated_conv_min_seconds(
        run["config"]["model"], run["size"], rows,
        run["peaks"]["flops_per_s"], run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (measured_ms / 1e3)
