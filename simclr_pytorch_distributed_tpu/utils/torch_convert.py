"""Reference-checkpoint interoperability, BOTH directions:
torch ``.pth`` <-> orbax payload.

The reference saves ``{'opt', 'model', 'optimizer', 'epoch'}`` via ``torch.save``
(``util.py:87-96``), where ``'model'`` is the DDP-wrapped ``SupConResNet``
state_dict — every key carries a ``'module.'`` prefix that the probe strips on
load (``main_linear.py:125-142``). This module converts that layout into this
framework's orbax ``model`` payload (``{'params', 'batch_stats'}``) so a
reference-pretrained encoder can be probed/warm-started here directly, and
exports this framework's checkpoints back into the reference's exact layout so
encoders pretrained HERE can be consumed by the reference's probe or any torch
tooling built around its checkpoints:

    # import: reference .pth -> orbax dir usable as --ckpt
    python -m simclr_pytorch_distributed_tpu.utils.torch_convert \
        path/to/ckpt_epoch_100.pth out_dir/
    python main_linear.py --ckpt out_dir/ ...

    # export: any checkpoint/run dir -> reference-format .pth
    python -m simclr_pytorch_distributed_tpu.utils.torch_convert \
        --export work_space/..._models/<run>/last out.pth

Layout mapping (torch ``resnet_big.py`` -> ``models/``):

- conv weights OIHW -> HWIO (XLA:TPU's native conv kernel layout);
- linear weights ``[out, in]`` -> ``[in, out]``;
- ``bn.weight/bias`` -> ``params/../scale|bias``; ``running_mean/var`` ->
  ``batch_stats/../mean|var``; ``num_batches_tracked`` dropped (torch keeps it
  for momentum=None mode, never used by the reference's momentum=0.1 BNs);
- ``encoder.layer{L}.{i}.conv{k}`` -> ``encoder/layer{L}_block{i}/Conv_{k-1}``,
  ``shortcut.0/1`` -> ``shortcut_conv``/``shortcut_bn``;
- ``head.0/head.2`` (mlp) -> ``proj_head/fc1|fc2``; ``head`` (linear) ->
  ``proj_head/fc``.

Architecture (resnet18/34/50/101, mlp/linear head) is inferred from the
state_dict itself — no unpickling of the reference's argparse Namespace needed.
torch is imported lazily: only conversion needs it, the framework does not.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, Tuple

import numpy as np

# torch layer index -> (stage sizes -> model name); resnet_big.py:121-142
_STAGES_TO_NAME = {
    (2, 2, 2, 2): "resnet18",
    (3, 4, 6, 3): None,  # resnet34 (BasicBlock) or resnet50 (Bottleneck)
    (3, 4, 23, 3): "resnet101",
    (1, 1, 1, 1): "resnet10",  # this framework's smoke-test extension
}


def strip_module_prefix(state_dict: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Remove the DDP ``'module.'`` prefix (main_linear.py:129-133)."""
    out = {}
    for k, v in state_dict.items():
        out[k[len("module."):] if k.startswith("module.") else k] = v
    return out


def infer_architecture(sd: Dict[str, np.ndarray]) -> Tuple[str, str, int]:
    """(model_name, head, feat_dim) from state_dict keys/shapes alone."""
    stages = []
    for layer in (1, 2, 3, 4):
        blocks = {
            int(m.group(1))
            for k in sd
            if (m := re.match(rf"encoder\.layer{layer}\.(\d+)\.", k))
        }
        stages.append(max(blocks) + 1 if blocks else 0)
    bottleneck = any(k.startswith("encoder.layer1.0.conv3") for k in sd)
    stages = tuple(stages)
    name = _STAGES_TO_NAME.get(stages)
    if name is None and stages == (3, 4, 6, 3):
        name = "resnet50" if bottleneck else "resnet34"
    if name is None:
        raise ValueError(f"unrecognized stage sizes {stages}")

    if "head.0.weight" in sd:
        head, feat_dim = "mlp", int(sd["head.2.weight"].shape[0])
    elif "head.weight" in sd:
        head, feat_dim = "linear", int(sd["head.weight"].shape[0])
    else:
        # A headless payload would convert "successfully" but then fail a
        # late, cryptic orbax restore against SupConResNet's proj_head tree —
        # fail loudly here instead.
        raise ValueError(
            "state_dict has no head.* keys (encoder-only checkpoint); the "
            "reference's save_model always includes the projection head "
            "(util.py:87-96), and --ckpt loads expect it"
        )
    return name, head, feat_dim


def _set(tree: dict, path: Tuple[str, ...], value: np.ndarray) -> None:
    node = tree
    for p in path[:-1]:
        node = node.setdefault(p, {})
    node[path[-1]] = value


def _conv(w: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(np.transpose(w, (2, 3, 1, 0)))  # OIHW -> HWIO


def torch_state_dict_to_variables(state_dict) -> dict:
    """Reference ``SupConResNet`` state_dict -> ``{'params', 'batch_stats'}``.

    Accepts torch tensors or numpy arrays; ``'module.'`` prefixes are stripped.
    Raises on any unconsumed key so a layout drift cannot pass silently.
    """
    sd = {
        k: (v.detach().cpu().numpy() if hasattr(v, "detach") else np.asarray(v))
        for k, v in strip_module_prefix(state_dict).items()
    }
    params: dict = {}
    stats: dict = {}
    consumed = set()

    def take(key: str) -> np.ndarray:
        consumed.add(key)
        return np.asarray(sd[key], np.float32)

    def map_bn(src: str, dst: Tuple[str, ...]) -> None:
        _set(params, dst + ("scale",), take(f"{src}.weight"))
        _set(params, dst + ("bias",), take(f"{src}.bias"))
        _set(stats, dst + ("mean",), take(f"{src}.running_mean"))
        _set(stats, dst + ("var",), take(f"{src}.running_var"))
        if f"{src}.num_batches_tracked" in sd:
            consumed.add(f"{src}.num_batches_tracked")

    def map_linear(src: str, dst: Tuple[str, ...]) -> None:
        _set(params, dst + ("kernel",), take(f"{src}.weight").T.copy())
        _set(params, dst + ("bias",), take(f"{src}.bias"))

    for key in sd:
        if key in consumed:
            continue
        if key == "encoder.conv1.weight":
            _set(params, ("encoder", "conv1", "kernel"), _conv(take(key)))
        elif key.startswith("encoder.bn1."):
            map_bn("encoder.bn1", ("encoder", "bn1"))
        elif m := re.match(r"encoder\.layer(\d)\.(\d+)\.(conv|bn)(\d)\.", key):
            layer, block, kind, idx = m.groups()
            scope = ("encoder", f"layer{layer}_block{block}")
            if kind == "conv":
                _set(
                    params, scope + (f"Conv_{int(idx) - 1}", "kernel"),
                    _conv(take(f"encoder.layer{layer}.{block}.conv{idx}.weight")),
                )
            else:
                map_bn(f"encoder.layer{layer}.{block}.bn{idx}", scope + (f"bn{idx}",))
        elif m := re.match(r"encoder\.layer(\d)\.(\d+)\.shortcut\.(\d)\.", key):
            layer, block, idx = m.groups()
            scope = ("encoder", f"layer{layer}_block{block}")
            src = f"encoder.layer{layer}.{block}.shortcut.{idx}"
            if idx == "0":
                _set(params, scope + ("shortcut_conv", "kernel"), _conv(take(f"{src}.weight")))
            else:
                map_bn(src, scope + ("shortcut_bn",))
        elif key.startswith("head.0."):
            map_linear("head.0", ("proj_head", "fc1"))
        elif key.startswith("head.2."):
            map_linear("head.2", ("proj_head", "fc2"))
        elif key.startswith("head.") and key.split(".")[1] in ("weight", "bias"):
            map_linear("head", ("proj_head", "fc"))

    leftover = set(sd) - consumed
    if leftover:
        raise ValueError(f"unmapped reference keys: {sorted(leftover)[:8]}")
    return {"params": params, "batch_stats": stats}


def _inv_conv(w: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(np.transpose(w, (3, 2, 0, 1)))  # HWIO -> OIHW


# models the reference can actually construct (resnet_big.py model_dict);
# exports of framework-only extensions (resnet10) would produce a .pth the
# reference cannot consume, so export refuses them.
_REFERENCE_MODELS = frozenset({"resnet18", "resnet34", "resnet50", "resnet101"})


def _bn_stats(stats: dict, path: Tuple[str, ...]) -> dict:
    """Resolve one BN's ``batch_stats`` node, raising ValueError (this
    module's stated error contract) naming the missing node instead of a bare
    KeyError from deep indexing."""
    node = stats
    for p in path:
        if not isinstance(node, dict) or p not in node:
            raise ValueError(
                "variables tree is missing batch_stats for BN node "
                f"'{'/'.join(path)}' — cannot express it in the reference "
                "layout (was the checkpoint saved without batch_stats?)"
            )
        node = node[p]
    for leaf in ("mean", "var"):
        if leaf not in node:
            raise ValueError(
                f"batch_stats node '{'/'.join(path)}' has no '{leaf}' — "
                "cannot express it in the reference layout"
            )
    return node


def variables_to_torch_state_dict(variables: dict) -> Dict[str, np.ndarray]:
    """Inverse of :func:`torch_state_dict_to_variables`: this framework's
    ``{'params', 'batch_stats'}`` -> the reference ``SupConResNet`` state_dict
    layout (``resnet_big.py:156-183``), as numpy arrays without the DDP
    ``'module.'`` prefix. ``num_batches_tracked`` is emitted as 0 for every BN
    (torch's fresh-module value; the reference's momentum=0.1 BNs never read
    it) so ``load_state_dict(strict=True)`` sees a complete dict. Raises on
    any tree node it cannot represent in the reference layout (e.g. a token
    encoder's blocks), so a lossy export cannot pass silently."""
    params = variables["params"]
    stats = variables.get("batch_stats", {})
    sd: Dict[str, np.ndarray] = {}

    def put(key: str, arr) -> None:
        sd[key] = np.ascontiguousarray(np.asarray(arr, np.float32))

    def put_bn(dst: str, p: dict, stats_path: Tuple[str, ...]) -> None:
        s = _bn_stats(stats, stats_path)
        put(f"{dst}.weight", p["scale"])
        put(f"{dst}.bias", p["bias"])
        put(f"{dst}.running_mean", s["mean"])
        put(f"{dst}.running_var", s["var"])
        sd[f"{dst}.num_batches_tracked"] = np.asarray(0, np.int64)

    def put_linear(dst: str, p: dict) -> None:
        put(f"{dst}.weight", np.asarray(p["kernel"], np.float32).T)
        put(f"{dst}.bias", p["bias"])

    for name, sub in params["encoder"].items():
        if name == "conv1":
            put("encoder.conv1.weight", _inv_conv(sub["kernel"]))
        elif name == "bn1":
            put_bn("encoder.bn1", sub, ("encoder", "bn1"))
        elif m := re.match(r"layer(\d)_block(\d+)$", name):
            layer, block = m.groups()
            for part, leaf in sub.items():
                dst = f"encoder.layer{layer}.{block}"
                if cm := re.match(r"Conv_(\d)$", part):
                    put(f"{dst}.conv{int(cm.group(1)) + 1}.weight",
                        _inv_conv(leaf["kernel"]))
                elif re.match(r"bn\d$", part):
                    put_bn(f"{dst}.{part}", leaf, ("encoder", name, part))
                elif part == "shortcut_conv":
                    put(f"{dst}.shortcut.0.weight", _inv_conv(leaf["kernel"]))
                elif part == "shortcut_bn":
                    put_bn(f"{dst}.shortcut.1", leaf, ("encoder", name, part))
                else:
                    raise ValueError(
                        f"cannot express {name}/{part} in the reference layout"
                    )
        else:
            raise ValueError(
                f"cannot express encoder/{name} in the reference layout"
            )

    head = params["proj_head"]
    if "fc1" in head:
        put_linear("head.0", head["fc1"])
        put_linear("head.2", head["fc2"])
    elif "fc" in head:
        put_linear("head", head["fc"])
    else:
        raise ValueError(f"unrecognized proj_head tree: {sorted(head)}")
    return sd


def export_reference_checkpoint(
    ckpt_path: str, out_pth: str, epoch: "int | None" = None,
    allow_missing_meta: bool = False,
) -> dict:
    """This framework's checkpoint -> a reference-format ``.pth``.

    The exported file matches ``util.py:87-96``'s ``save_model`` layout —
    ``{'opt', 'model' ('module.'-prefixed state_dict), 'optimizer', 'epoch'}``
    — so the reference's own ``main_linear.py:125-142`` load path (and any
    torch tooling built around its checkpoints) consumes it directly.
    ``ckpt_path`` is a dir holding a ``model`` payload (ckpt_epoch_N / last /
    a torch_convert output) or a run dir (resolved to its latest complete
    checkpoint). Returns ``{'model_name', 'head', 'feat_dim', 'epoch',
    'path'}``."""
    import torch  # lazy: only conversion needs torch

    import orbax.checkpoint as ocp

    from simclr_pytorch_distributed_tpu.utils.checkpoint import (
        MODEL_LAYOUT_VERSION,
        resolve_resume_path,
    )

    ckpt_path = os.path.abspath(ckpt_path)
    if not os.path.isdir(os.path.join(ckpt_path, "model")):
        ckpt_path = resolve_resume_path(ckpt_path)
    meta_path = os.path.join(ckpt_path, "meta.json")
    meta = {}
    if not os.path.exists(meta_path):
        # meta.json is both the save-completeness marker (utils/checkpoint.py
        # stamps it atomically after the payload) and the only carrier of
        # model_layout; exporting without it would skip the layout guard
        # below — the 'lossy export cannot pass silently' contract.
        if not allow_missing_meta:
            raise ValueError(
                f"{ckpt_path} has no meta.json — the checkpoint may be an "
                "incomplete save, and its model layout cannot be verified; "
                "pass --allow-missing-meta to export anyway"
            )
    else:
        with open(meta_path) as f:
            meta = json.load(f)
        saved_layout = meta.get("model_layout", 1)
        if saved_layout != MODEL_LAYOUT_VERSION:
            # torch's padding=1 convs match this build's v2 semantics only; a
            # pre-v2 checkpoint would strict-load into the reference cleanly
            # yet be silently wrong — refuse, per this module's contract.
            raise ValueError(
                f"{ckpt_path} was saved at model layout v{saved_layout} but "
                f"the reference's conv semantics require v{MODEL_LAYOUT_VERSION}"
                f"; re-train or re-save under the current layout before export"
            )
    if epoch is None:
        epoch = meta.get("epoch")

    ckptr = ocp.StandardCheckpointer()
    variables = ckptr.restore(os.path.join(ckpt_path, "model"))
    ckptr.close()
    sd_np = variables_to_torch_state_dict(variables)
    sd = {f"module.{k}": torch.from_numpy(v) for k, v in sd_np.items()}
    model_name, head, feat_dim = infer_architecture(sd_np)
    if model_name not in _REFERENCE_MODELS:
        # e.g. resnet10: opt.model would name an architecture absent from the
        # reference's model_dict (resnet_big.py:121-142) — the .pth would
        # export "successfully" yet be unconsumable upstream.
        raise ValueError(
            f"'{model_name}' is a framework-only extension with no entry in "
            "the reference's model_dict — the exported .pth could not be "
            "loaded by the reference"
        )
    payload = {
        # the reference stores its argparse Namespace here; a plain dict keeps
        # the slot readable without importing anything of ours
        "opt": {
            "model": model_name, "head": head, "feat_dim": feat_dim,
            "exported_from": ckpt_path,
            "config": meta.get("config", {}),
        },
        "model": sd,
        "optimizer": {},  # reference stores SGD state; not transferable
        "epoch": int(epoch) if epoch is not None else 0,
    }
    out_pth = os.path.abspath(out_pth)
    os.makedirs(os.path.dirname(out_pth) or ".", exist_ok=True)
    torch.save(payload, out_pth)
    return {
        "model_name": model_name, "head": head, "feat_dim": feat_dim,
        "epoch": epoch, "path": out_pth,
    }


def convert_reference_checkpoint(pth_path: str, out_dir: str) -> dict:
    """Load a reference ``.pth`` and write this framework's orbax payload.

    Returns ``{'model_name', 'head', 'feat_dim', 'epoch', 'path'}``. The output
    dir is directly consumable by ``--ckpt`` (``load_pretrained_variables``
    accepts a dir holding a ``model`` payload).
    """
    import torch  # lazy: only conversion needs torch

    from simclr_pytorch_distributed_tpu.utils.checkpoint import (
        MODEL_LAYOUT_VERSION,
        _save_tree,
        _write_meta,
    )

    ckpt = torch.load(pth_path, map_location="cpu", weights_only=False)
    sd = ckpt["model"] if isinstance(ckpt, dict) and "model" in ckpt else ckpt
    sd = strip_module_prefix({k: v for k, v in sd.items()})
    model_name, head, feat_dim = infer_architecture(sd)
    variables = torch_state_dict_to_variables(sd)

    out_dir = os.path.abspath(out_dir)
    _save_tree(os.path.join(out_dir, "model"), variables)
    epoch = ckpt.get("epoch") if isinstance(ckpt, dict) else None
    _write_meta(out_dir, {
        "epoch": int(epoch) if epoch is not None else None,
        # torch weights are padding=1 semantics == this build's v2 layout
        "model_layout": MODEL_LAYOUT_VERSION,
        "config": {
            "model": model_name, "head": head, "feat_dim": feat_dim,
            "converted_from": os.path.abspath(pth_path),
        },
    })
    info = {
        "model_name": model_name, "head": head, "feat_dim": feat_dim,
        "epoch": epoch, "path": out_dir,
    }
    return info


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(
        description="convert checkpoints between the reference's torch .pth "
                    "layout and this framework's orbax payload (both "
                    "directions)"
    )
    p.add_argument("src", help="reference .pth (import) or checkpoint/run dir "
                               "(--export)")
    p.add_argument("dst", help="output dir usable as --ckpt (import) or "
                               "output .pth path (--export)")
    p.add_argument(
        "--export", action="store_true",
        help="reverse direction: orbax checkpoint -> reference-format .pth",
    )
    p.add_argument(
        "--allow-missing-meta", action="store_true",
        help="export even when the checkpoint dir has no meta.json "
             "(completeness marker + model-layout carrier); epoch defaults "
             "to 0 and the layout guard is skipped",
    )
    args = p.parse_args(argv)
    if args.export:
        info = export_reference_checkpoint(
            args.src, args.dst, allow_missing_meta=args.allow_missing_meta
        )
    else:
        info = convert_reference_checkpoint(args.src, args.dst)
    print(json.dumps(info))


if __name__ == "__main__":
    main()
