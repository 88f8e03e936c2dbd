"""encoder.attn_kernel_ms_per_step (ms): layer "encoder", moves pretrain_imgs_per_s.

Device time a step of sparse attention's kernel pair
(``ops/sparse_attention.py``): the Mosaic custom calls of the compiled step
that carry the kernels' names (``sparse_attention_fwd``, ``sparse_attention_bwd``
and their ``.<n>`` copies), forward, the row group's recomputed forward and
backward, which sit inside the attention layers' loops under
``encoder/block<k>/attn`` and are part of ``encoder.attn_ms_per_step``; the
expert layers' and the loss's Mosaic calls are not counted. Where the step
holds no such call (XLA's path: a ResNet, the parent commit, a CPU run's
geometry) there is nothing to read and nothing is reported.
Source: device trace."""

import trace_reduce as tr

KERNELS = ("sparse_attention_fwd", "sparse_attention_bwd")


def read(run):
    if not run.get("stretches") or not run.get("kinds"):
        return None
    ours = {name for name, kind in run["kinds"].items()
            if kind == "pallas" and name.split(".")[0] in KERNELS}
    if not ours:
        return None
    t = tr.per_step_max(run["planes"], run["stretches"], lambda e: tr.instruction(e) in ours)
    return 1e3 * t if t > 0 else None
