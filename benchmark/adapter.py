"""Names of the program's parameter tree against the reference's.

The reference names its arrays itself (``reference.param_spec``); the program
keeps them in a Flax tree. This is the one place that knows both spellings,
so that the harness can hand the program the weights the reference made, and
read the program's gradients, updates and BN statistics back under the
reference's names.
"""

from __future__ import annotations

import re

import jax

_BLOCK = re.compile(r"layer(\d+)_block(\d+)$")
_LEAF = {"kernel": None, "scale": "scale", "bias": "bias", "mean": "mean", "var": "var"}


def reference_name(path: tuple) -> str:
    """('encoder', 'layer2_block0', 'Conv_1', 'kernel') -> 'layer2.0/conv2'."""
    top, *rest = path
    if top == "proj_head":
        layer, leaf = rest
        return f"head/{layer}/{'w' if leaf == 'kernel' else 'b'}"
    if top != "encoder":
        raise KeyError(f"no reference name for {path}")
    if len(rest) == 2:  # the stem: conv1 / bn1
        module, leaf = rest
        prefix = "stem"
    else:
        block, module, leaf = rest
        m = _BLOCK.match(block)
        if not m:
            raise KeyError(f"no reference name for {path}")
        prefix = f"layer{m.group(1)}.{m.group(2)}"
    if module.startswith("shortcut_"):
        prefix, module = f"{prefix}/shortcut", module[len("shortcut_"):]
    if module.startswith("Conv_"):
        module = f"conv{int(module[5:]) + 1}"
    if prefix.startswith("stem") or prefix.endswith("shortcut"):
        module = module.rstrip("1")  # stem conv1/bn1, shortcut conv/bn
    if leaf == "kernel":
        return f"{prefix}/{module}"
    return f"{prefix}/{module}/{_LEAF[leaf]}"


def _paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [tuple(k.key for k in path) for path, _ in flat], treedef


def to_program(ref_params: dict, program_params_shape):
    """The reference's arrays laid out as the program's tree. Every array is
    used exactly once and every shape has to agree."""
    paths, treedef = _paths(program_params_shape)
    names = [reference_name(p) for p in paths]
    if sorted(names) != sorted(ref_params):
        odd = set(names) ^ set(ref_params)
        raise ValueError(f"program and reference disagree on the arrays: {sorted(odd)}")
    leaves = []
    for name, shape in zip(names, jax.tree.leaves(program_params_shape)):
        if tuple(shape.shape) != tuple(ref_params[name].shape):
            raise ValueError(f"{name}: program {shape.shape}, reference {ref_params[name].shape}")
        leaves.append(ref_params[name])
    return jax.tree.unflatten(treedef, leaves)


def to_reference(program_tree) -> dict:
    """A program-shaped tree (parameters, gradients, momentum, batch
    statistics) as a flat dict under the reference's names."""
    paths, _ = _paths(program_tree)
    return dict(zip((reference_name(p) for p in paths), jax.tree.leaves(program_tree)))
