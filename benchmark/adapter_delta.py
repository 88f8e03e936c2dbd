"""Names of the token encoder's parameter tree against the reference's
(``reference_delta.param_spec``) for a preset of Qwen3-Next's block: a tree of
``encoder/block<i>/attn`` (Gated DeltaNet or gated attention) and
``encoder/block<i>/moe`` (routed experts and the gated shared expert), whose
running statistics are ``encoder/block<i>/{prob,load}_mean``.

``to_program`` and ``to_reference`` are ``adapter_latent``'s, reading this
file's ``reference_name``.
"""

from __future__ import annotations

import re
import types

import adapter as base
import adapter_latent

_BLOCK = re.compile(r"block(\d+)$")
# both mixers' leaves under one table: only "norm" and "o" are shared
_ATTN = {"norm": "norm1", "o": "wo", "qkvz": "w_qkvz", "ba": "w_ba", "conv": "conv",
         "A_log": "A_log", "dt_bias": "dt_bias", "out_norm": "out_norm", "q": "wq", "k": "wk",
         "v": "wv", "q_norm": "q_norm", "k_norm": "k_norm"}
_MOE = {name: name for name in ("router", "w_gate", "w_up", "w_down", "shared_gate",
                                "shared_up", "shared_down", "shared_expert_gate")}
_MOE["norm"] = "norm2"
_PARTS = {"attn": _ATTN, "moe": _MOE}


def reference_name(path: tuple) -> str:
    """('encoder', 'block2', 'attn', 'qkvz') -> 'layer2/w_qkvz'."""
    top, *rest = path
    if top == "proj_head":
        return base.reference_name(path)
    if top == "encoder" and rest == ["final_norm"]:
        return "final_norm"
    if top == "encoder" and rest[0] == "patch_embed":
        return "embed/w" if rest[1] == "kernel" else "embed/b"
    block = _BLOCK.match(rest[0]) if top == "encoder" else None
    if block:
        layer = f"layer{block.group(1)}"
        if len(rest) == 2 and rest[1] in ("prob_mean", "load_mean"):
            return f"{layer}/{rest[1]}"
        names = _PARTS.get(rest[1], {}) if len(rest) == 3 else {}
        if rest[-1] in names:
            return f"{layer}/{names[rest[-1]]}"
    raise KeyError(f"no reference name for {path}")


def _with_our_names(fn):
    return types.FunctionType(fn.__code__, dict(vars(adapter_latent), reference_name=reference_name),
                              fn.__name__, fn.__defaults__)


to_program = _with_our_names(adapter_latent.to_program)
to_reference = _with_our_names(adapter_latent.to_reference)
