"""The encoder against the commit before the ``--conv_impl`` ladder went.

``tests/encoder_golden.json`` was written by running this file as a script on
commit ``e91feee`` (``PYTHONPATH=. python tests/test_encoder_unchanged.py`` from that
tree's root, this file copied into it): the parameter and statistics trees of
every ``SupConResNet`` a checkpoint can hold, and the numbers of the ResNets'
train step, eval path and gradient at a small size. A checkpoint written
before the ladder's removal restores after it, and what the one remaining conv
path computes is what XLA's path computed then. Beside them: every benchmark
configuration still parses, and what is left of ``--conv_impl`` means one thing.
"""

import functools
import glob
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_pytorch_distributed_tpu import config as config_lib
from simclr_pytorch_distributed_tpu.models import SupConResNet

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN_PATH = os.path.join(HERE, "encoder_golden.json")
TREE_MODELS = ("resnet10", "resnet18", "resnet34", "resnet50", "resnet101", "keye-vl2-tiny")
NUMBER_MODELS = ("resnet10", "resnet18", "resnet34", "resnet50")
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}
# of each tensor's norm: wide enough for another CPU's vector width, and a
# dropped ReLU or a moved BN reads O(1)
TOLERANCE = {"fp32": 1e-4, "bf16": 3e-2}
FEAT_DIM = 8  # a narrow head keeps the recorded embeddings a few kilobytes


def tree_spec(model_name: str) -> dict:
    """``{"collection/module/submodule": digest}`` of a fresh ``SupConResNet``:
    one digest a residual block (or stem, head, token layer) over its leaves'
    paths, shapes and dtypes, so that a difference names the block and the
    file stays small."""
    model = SupConResNet(model_name=model_name)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((2, 16, 16, 3)), train=False))
    groups: dict = {}
    for collection in ("params", "batch_stats"):
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes[collection]):
            keys = [collection] + [p.key for p in path]
            groups.setdefault("/".join(keys[:3]), []).append(
                ("/".join(keys), tuple(leaf.shape), str(leaf.dtype)))
    return {
        group: f"{len(leaves)}:{hashlib.sha256(repr(sorted(leaves)).encode()).hexdigest()[:12]}"
        for group, leaves in groups.items()
    }


def compact(values) -> list:
    """Six digits: the tolerances are 1e-4 and up, and the file stays small."""
    return [float(f"{v:.6g}") for v in np.asarray(values, np.float32).ravel()]


def leaf_norms(tree) -> list:
    """In ``jax.tree.leaves`` order; ``leaf_names`` has the paths."""
    return compact([jnp.linalg.norm(leaf.astype(jnp.float32)) for leaf in jax.tree.leaves(tree)])


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


@functools.lru_cache(maxsize=None)
def numbers(model_name: str, dtype_name: str) -> dict:
    """Train-mode embeddings with the statistics they leave, eval-mode
    embeddings, and the gradient of the train-mode sum of squares, for 8 rows
    of 16x16 from a fixed key; embeddings flat, trees as per-leaf norms, and
    under ``names`` (not recorded) the leaves' paths for a failure's message."""
    model = SupConResNet(model_name=model_name, feat_dim=FEAT_DIM, dtype=DTYPES[dtype_name])
    x = jax.random.normal(jax.random.key(7), (8, 16, 16, 3))
    variables = model.init(jax.random.key(0), x, train=False)

    @jax.jit
    def run(variables):
        def train_loss(params):
            out, mutated = model.apply(
                {"params": params, "batch_stats": variables["batch_stats"]}, x,
                train=True, mutable=["batch_stats"])
            return jnp.sum(out.astype(jnp.float32) ** 2), (out, mutated["batch_stats"])

        (_, (out, stats)), grads = jax.value_and_grad(train_loss, has_aux=True)(
            variables["params"])
        return out, stats, model.apply(variables, x, train=False), grads

    out, stats, eval_out, grads = run(variables)
    return {
        "train": {"features": compact(out), "batch_stats": leaf_norms(stats)},
        "eval": {"features": compact(eval_out)},
        "grad": {"leaf_norms": leaf_norms(grads)},
        "names": {"batch_stats": leaf_names(stats), "leaf_norms": leaf_names(grads)},
    }


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN_PATH) as f:
        return json.load(f)


@pytest.mark.parametrize("model_name", TREE_MODELS)
def test_variable_trees_are_the_parents(golden, model_name):
    assert tree_spec(model_name) == golden["trees"][model_name]


@pytest.mark.parametrize("quantity", ["train", "eval", "grad"])
@pytest.mark.parametrize("dtype_name", list(DTYPES))
@pytest.mark.parametrize("model_name", NUMBER_MODELS)
def test_numbers_are_the_parents(golden, model_name, dtype_name, quantity):
    want = golden["numbers"][f"{model_name}/{dtype_name}"][quantity]
    ours = numbers(model_name, dtype_name)
    tol = TOLERANCE[dtype_name]
    for key, recorded in want.items():
        got, recorded = np.asarray(ours[quantity][key]), np.asarray(recorded)
        assert got.shape == recorded.shape, key
        if key == "features":  # one tensor
            assert np.linalg.norm(got - recorded) <= tol * np.linalg.norm(recorded)
            continue
        # a norm a leaf: each against its own, the smallest against a
        # thousandth of the largest, where rounding and not the model decides
        off = np.abs(got - recorded) > tol * np.maximum(recorded, 1e-3 * recorded.max())
        assert not off.any(), [
            (ours["names"][key][i], got[i], recorded[i]) for i in np.flatnonzero(off)]


CONFIG_FILES = sorted(glob.glob(os.path.join(HERE, "..", "benchmark", "configs", "*.json")))


@pytest.mark.parametrize("path", CONFIG_FILES, ids=os.path.basename)
def test_benchmark_configuration_parses(path, tmp_path):
    """Read only: a flag that goes from ``config.py`` while a configuration
    still passes it would otherwise fail on the chip alone. The four appended
    flags are ``benchmark/run.py``'s (``drive``)."""
    with open(path) as f:
        flags = json.load(f)["flags"]
    cfg = config_lib.parse_supcon(flags + [
        "--batch_size", "256", "--seed", "1", "--dataset", "synthetic",
        "--workdir", str(tmp_path)])
    assert cfg.model == flags[flags.index("--model") + 1]


def test_benchmark_configurations_found():
    assert len(CONFIG_FILES) >= 3


def built_model(extra, workdir):
    from simclr_pytorch_distributed_tpu.train.supcon import build

    cfg = config_lib.parse_supcon([
        "--model", "resnet10", "--size", "8", "--batch_size", "4", "--dataset",
        "synthetic", "--workdir", str(workdir)] + extra)
    return build(cfg, steps_per_epoch=2)[0]


@pytest.mark.parametrize("value", ["auto", "xla"])
def test_conv_impl_remnant_means_one_path(value, tmp_path):
    assert built_model(["--conv_impl", value], tmp_path) == built_model([], tmp_path)


def test_conv_impl_pallas_is_an_invalid_choice(tmp_path, capsys):
    with pytest.raises(SystemExit):
        built_model(["--conv_impl", "pallas"], tmp_path)
    assert "invalid choice: 'pallas'" in capsys.readouterr().err


if __name__ == "__main__":
    # run on the commit the goldens are taken from
    with open(GOLDEN_PATH, "w") as f:
        json.dump({
            "commit": "e91feeebe1133d66c66af6045e1c12f2cc889fb3",
            "trees": {name: tree_spec(name) for name in TREE_MODELS},
            "numbers": {
                f"{m}/{d}": {k: v for k, v in numbers(m, d).items() if k != "names"}
                for m in NUMBER_MODELS for d in DTYPES},
        }, f, separators=(",", ":"))
