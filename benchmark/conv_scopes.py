"""Device time by scope for an encoder over patch tokens with gated short
convolutions: ``token_scopes``' machinery (the compiled step's ``op_name``s,
every busy instant to one operation, the compiler's own kernels by name),
taken as it is and run over this file's buckets, as ``delta_scopes`` runs it
over its own.

Under ``encoder`` the innermost of these scopes decides: ``attn`` (flax's
module path ``encoder/block<k>/attn``: here the attention layer whole),
``conv_mixer`` (``named_scope`` around the whole of a gated short
convolution under its ``attn``: norm, projections), ``gated_conv``
(``named_scope`` inside it: ``B x``, the convolution, ``C u``), ``moe`` and
``experts`` as ``token_scopes`` has them; what sits directly under
``encoder`` (patch embedding, the dense layer, final norm, pooling) is
``embed``.

    python benchmark/conv_scopes.py <trace dir>

prints the table for a ``--trace_dir`` capture, as ``token_scopes.py`` does.
"""

from __future__ import annotations

import sys
import types

import scope_reduce as sr
import token_scopes as ts
import trace_reduce as tr

INNER = ts.INNER + ("conv_mixer", "gated_conv")
BUCKETS = ("data", "aug", "embed") + INNER + ("head", "loss", "optimizer", "ring")
# a gated short-convolution layer, whole
CONV = ("conv_mixer", "gated_conv")


def _over_our_buckets(fn):
    """``token_scopes``' function ``fn``, reading this file's ``INNER``,
    ``BUCKETS`` and ``bucket_of``."""
    return types.FunctionType(fn.__code__, _GLOBALS, fn.__name__, fn.__defaults__)


_GLOBALS = dict(vars(ts), INNER=INNER, BUCKETS=BUCKETS, __doc__=__doc__)
bucket_of = _GLOBALS["bucket_of"] = _over_our_buckets(ts.bucket_of)
scope_map = _GLOBALS["scope_map"] = _over_our_buckets(ts.scope_map)
table = _GLOBALS["table"] = _over_our_buckets(ts.table)
main = _over_our_buckets(ts.main)


def is_conv_step(hlo_text: str) -> bool:
    """Whether the step's text holds a gated short convolution's scopes."""
    return ts.is_token_step(hlo_text) and "/gated_conv/" in hlo_text


def scope_seconds(run: dict):
    """{"by_scope", "busy_s", "steps"} over the steady stretch of the chip
    that idles most, computed once a run; None where there is no trace, no
    text, or the step has no gated short convolution."""
    if "conv_scope_seconds" not in run:
        run["conv_scope_seconds"] = None
        # only where the configuration's count of operations knows the layer
        ours = hasattr(run.get("flops"), "gated_conv_min_seconds")
        program = sr.program_text() if ours and run.get("stretches") else None
        if program is not None and is_conv_step(program[1]):
            plane = run["planes"][run["worst"]]
            t0, t1, steps = run["stretches"][run["worst"]]
            run["conv_scope_seconds"] = {
                "by_scope": sr.seconds_by_scope(plane, t0, t1, scope_map(program[1])),
                "busy_s": tr.busy_seconds(plane, t0, t1), "steps": steps}
    return run["conv_scope_seconds"]


def ms_per_step(run: dict, buckets):
    got = scope_seconds(run)
    if got is None:
        return None
    return 1e3 * sr.bucket_seconds(got["by_scope"], buckets) / got["steps"]


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
