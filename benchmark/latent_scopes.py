"""Device time by scope for an encoder over patch tokens with latent
attention, a dense leading layer and shared experts: ``token_scopes``'
machinery (the compiled step's ``op_name``s, every busy instant to one
operation) with the buckets of ``models/token_encoder.py``'s step for such a
preset.

Under ``encoder`` the innermost of these scopes decides: ``attn`` (flax's
module path ``encoder/block<k>/attn``: the pre-norm, the query and output
projections, the query's rotary turn), ``latent`` (``named_scope`` inside it:
``W_kva``, the latent's norm, ``W_kvb``, the rotary key's turn and its
broadcast over the heads), ``attn_core`` (``named_scope`` inside it: scores,
causal mask, softmax, values), ``mlp`` (``encoder/block<k>/mlp``: a dense
layer's norm and gated MLP), ``moe`` (``encoder/block<k>/moe``: norm, router,
top-k, sorting, gather and scatter), ``shared`` (``named_scope`` inside it:
the shared experts' gated MLP) and ``experts`` (``named_scope`` inside it,
and the compiler's own ``ragged-dot`` kernels by name, as ``token_scopes``
finds them); what sits directly under ``encoder`` (patch embedding, final
norm, pooling) is ``embed``.

    python benchmark/latent_scopes.py <trace dir>

prints the table for a ``--trace_dir`` capture, as ``token_scopes.py`` does.
"""

from __future__ import annotations

import os
import sys

import scope_reduce as sr
import token_scopes as ts
import trace_reduce as tr

INNER = ("attn", "latent", "attn_core", "mlp", "moe", "shared", "experts")
BUCKETS = ("data", "aug", "embed") + INNER + ("head", "loss", "optimizer", "ring")


def bucket_of(op_name: str):
    """(bucket, direction) of one ``op_name``, or None when it names none."""
    if not op_name.startswith("jit("):
        return None
    bucket = None
    for name in sr._scope_names(op_name):
        if name in ts._STEP_SCOPES or name in INNER:
            bucket = name
        elif name == "proj_head":
            bucket = "head"
        elif name == "encoder":
            bucket = "embed"
    if bucket is None:
        return None
    return bucket, "bwd" if "transpose(" in op_name else "fwd"


def scope_map(hlo_text: str) -> dict:
    """``token_scopes.scope_map`` with this file's buckets: an instruction's
    own ``op_name`` decides, for a fusion without one the majority of its
    body, and for the compiler's own kernels their name."""
    own, votes, calls, kernels, body_of = {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = tr._COMPUTATION.match(line)
        if m:
            body_of = m.group(1)
            continue
        m = tr._INSTRUCTION.match(line)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        meta = sr._OP_NAME.search(line)
        where = bucket_of(meta.group(1)) if meta else None
        kernel = next((b for stem, b in ts.KERNEL_BUCKETS.items() if name.startswith(stem)), None)
        if kernel is not None:
            kernels[name] = (kernel, body_of)
        elif where is not None:
            own[name] = where
            if body_of is not None:
                tally = votes.setdefault(body_of, {})
                tally[where] = tally.get(where, 0) + 1
        elif opcode == "fusion":
            c = tr._CALLS.search(line)
            if c:
                calls[name] = c.group(1)
    for name, body in calls.items():
        tally = votes.get(body)
        if tally:
            own[name] = max(sorted(tally), key=tally.get)
    for name, (bucket, body) in kernels.items():
        directions = {}
        for (_, d), n in votes.get(body, {}).items():
            directions[d] = directions.get(d, 0) + n
        own[name] = (bucket, max(sorted(directions), key=directions.get) if directions else "fwd")
    return own


def is_latent_step(hlo_text: str) -> bool:
    """Whether the step's text holds a latent-attention layer's scopes."""
    return "/encoder/block0/attn/" in hlo_text and "/latent/" in hlo_text


def scope_seconds(run: dict):
    """{"by_scope", "busy_s", "steps"} over the steady stretch of the chip
    that idles most, computed once a run; None where there is no trace, no
    text, or the step has no latent-attention layer."""
    if "latent_scope_seconds" not in run:
        run["latent_scope_seconds"] = None
        # only where the configuration's count of operations knows the layer
        ours = hasattr(run.get("flops"), "attn_core_min_seconds")
        program = sr.program_text() if ours and run.get("stretches") else None
        if program is not None and is_latent_step(program[1]):
            plane = run["planes"][run["worst"]]
            t0, t1, steps = run["stretches"][run["worst"]]
            run["latent_scope_seconds"] = {
                "by_scope": sr.seconds_by_scope(plane, t0, t1, scope_map(program[1])),
                "busy_s": tr.busy_seconds(plane, t0, t1), "steps": steps}
    return run["latent_scope_seconds"]


def ms_per_step(run: dict, buckets):
    got = scope_seconds(run)
    if got is None:
        return None
    return 1e3 * sr.bucket_seconds(got["by_scope"], buckets) / got["steps"]


def table(by_scope: dict, busy_s: float, steps: int) -> str:
    rows = [f"{'bucket':<14}{'fwd ms/step':>13}{'bwd ms/step':>13}{'share':>9}"]
    for bucket in BUCKETS + (sr.UNATTRIBUTED,):
        both = sr.bucket_seconds(by_scope, (bucket,))
        bwd = sr.bucket_seconds(by_scope, (bucket,), "bwd")
        rows.append(f"{bucket:<14}{1e3 * (both - bwd) / steps:>13.3f}{1e3 * bwd / steps:>13.3f}"
                    f"{both / busy_s:>9.1%}")
    rows.append(f"{'busy':<14}{1e3 * busy_s / steps:>13.3f}{'':>13}{1:>9.1%}")
    return "\n".join(rows)


def main(argv) -> int:
    if len(argv) != 1 or argv[0].startswith("-"):
        sys.exit(__doc__)
    with open(os.path.join(argv[0], sr.STEP_PROGRAM_FILE)) as f:
        text = f.read()
    header = sr._MODULE.search(text)
    if header is None:
        sys.exit(f"{sr.STEP_PROGRAM_FILE}: no 'HloModule jit_<program>' line")
    program, scopes = header.group(1), scope_map(text)
    planes = tr.device_planes(tr.load_xplane(argv[0]))
    if not planes:
        print(f"{argv[0]}: no TPU plane in the trace")
    for plane in planes:
        stretch = tr.steady_stretch(plane, program)
        if stretch is None:
            print(f"{plane['name']}: no steady stretch of {program}")
            continue
        t0, t1, steps = stretch
        print(f"{plane['name']}: {steps} steps of {program}, {(t1 - t0) / 1e6 / steps:.3f} ms a step")
        print(table(sr.seconds_by_scope(plane, t0, t1, scopes), tr.busy_seconds(plane, t0, t1), steps))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
