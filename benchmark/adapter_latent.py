"""Names of the token encoder's parameter tree against the reference's
(``reference_latent.param_spec``) for a preset of Moonlight's block:
``adapter_tokens.py``'s part for a tree of ``encoder/block<i>/attn``
(latent attention), ``encoder/block<i>/mlp`` (a dense layer) or
``encoder/block<i>/moe`` (routed and shared experts), whose running
statistics are ``encoder/block<i>/{prob,load}_mean`` and
``encoder/block<i>/moe/route_bias``.
"""

from __future__ import annotations

import re

import jax

import adapter as base

_BLOCK = re.compile(r"block(\d+)$")
_ATTN = {"norm": "norm1", "q": "wq", "kv_a": "wkv_a", "kv_norm": "kv_norm", "kv_b": "wkv_b",
         "o": "wo"}
_MLP = {"norm": "norm2", "w_gate": "mlp_gate", "w_up": "mlp_up", "w_down": "mlp_down"}
_MOE = {name: name for name in ("router", "w_gate", "w_up", "w_down", "shared_gate",
                                "shared_up", "shared_down", "route_bias")}
_MOE["norm"] = "norm2"
_PARTS = {"attn": _ATTN, "mlp": _MLP, "moe": _MOE}


def reference_name(path: tuple) -> str:
    """('encoder', 'block2', 'attn', 'kv_a') -> 'layer2/wkv_a'."""
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


def to_program(ref_params: dict, program_params_shape):
    """The reference's arrays laid out as the program's tree. Every array is
    used exactly once and every shape has to agree."""
    paths, treedef = base._paths(program_params_shape)
    names = [reference_name(p) for p in paths]
    if sorted(names) != sorted(ref_params):
        raise ValueError("program and reference disagree on the arrays: "
                         f"{sorted(set(names) ^ set(ref_params))}")
    for name, shape in zip(names, jax.tree.leaves(program_params_shape)):
        if tuple(shape.shape) != tuple(ref_params[name].shape):
            raise ValueError(f"{name}: program {shape.shape}, reference {ref_params[name].shape}")
    return jax.tree.unflatten(treedef, [ref_params[name] for name in names])


def to_reference(program_tree) -> dict:
    """A program-shaped tree (parameters, momentum, running statistics) as a
    flat dict under the reference's names."""
    paths, _ = base._paths(program_tree)
    return dict(zip((reference_name(p) for p in paths), jax.tree.leaves(program_tree)))
