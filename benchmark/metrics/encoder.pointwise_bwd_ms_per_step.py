"""encoder.pointwise_bwd_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step of the one-kernel backward of Bottleneck's tail
(``ops/pointwise_bwd.py``): the Mosaic custom calls of the compiled step that
carry that kernel's name (``pointwise_bwd``, ``pointwise_bwd.<n>``), which sit
under ``encoder/layer<n>_block<k>`` and nowhere else; the loss's Mosaic calls
are not counted. Where the step holds no such call (a BasicBlock encoder, a
program without the kernel) there is nothing to read and nothing is reported.
Source: device trace."""

import trace_reduce as tr

KERNEL = "pointwise_bwd"


def read(run):
    if not run.get("stretches") or not run.get("kinds"):
        return None
    ours = {name for name, kind in run["kinds"].items()
            if kind == "pallas" and name.split(".")[0] == KERNEL}
    if not ours:
        return None
    t = tr.per_step_max(run["planes"], run["stretches"], lambda e: tr.instruction(e) in ours)
    return 1e3 * t if t > 0 else None
