"""Model shape / parameter-count / init tests.

Golden parameter counts were computed once from the reference architecture
definition (networks/resnet_big.py) with torch and hardcoded here, so any
architectural drift (widths, strides, shortcut placement, head sizes) fails.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_pytorch_distributed_tpu.models import (
    MODEL_DICT,
    LinearClassifier,
    SupCEResNet,
    SupConResNet,
)

# (encoder params, SupConResNet total params) from the reference model defs.
GOLDEN_COUNTS = {
    "resnet18": (11_168_832, 11_497_152),
    "resnet34": (21_276_992, 21_605_312),
    "resnet50": (23_500_352, 27_958_976),
    "resnet101": (42_492_480, 46_951_104),
}


def n_params(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("name", ["resnet18", "resnet50"])
def test_encoder_shape_and_params(name):
    model_fn, feat_dim = MODEL_DICT[name]
    model = model_fn()
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, feat_dim)
    assert n_params(variables["params"]) == GOLDEN_COUNTS[name][0]


@pytest.mark.parametrize("name", ["resnet34", "resnet101"])
def test_encoder_params_slow(name):
    model_fn, _ = MODEL_DICT[name]
    variables = jax.eval_shape(
        lambda: model_fn().init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=False)
    )
    assert n_params(variables["params"]) == GOLDEN_COUNTS[name][0]


def test_supcon_model_shape_and_params():
    model = SupConResNet(model_name="resnet50")
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 128)
    assert n_params(variables["params"]) == GOLDEN_COUNTS["resnet50"][1]
    # unnormalized output: norms should not all be ~1
    assert not np.allclose(np.linalg.norm(np.asarray(out), axis=1), 1.0, atol=1e-3)


def test_supcon_linear_head():
    model = SupConResNet(model_name="resnet18", head="linear")
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=False)
    )
    # encoder + single 512->128 linear
    assert n_params(variables["params"]) == GOLDEN_COUNTS["resnet18"][0] + 512 * 128 + 128


def test_linear_classifier_params():
    cls = LinearClassifier(model_name="resnet50", num_classes=10)
    variables = cls.init(jax.random.key(0), jnp.zeros((2, 2048)))
    assert n_params(variables["params"]) == 20_490
    assert cls.apply(variables, jnp.zeros((2, 2048))).shape == (2, 10)


def test_supce_params():
    model = SupCEResNet(model_name="resnet50", num_classes=10)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=True)
    )
    assert n_params(variables["params"]) == 23_520_842


def test_encode_matches_encoder_output():
    model = SupConResNet(model_name="resnet18")
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    feats = model.apply(variables, x, train=False, method=SupConResNet.encode)
    assert feats.shape == (2, 512)


def test_batch_stats_update_in_train_mode():
    model = SupConResNet(model_name="resnet18")
    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3)) + 3.0
    variables = model.init(jax.random.key(0), x, train=True)
    _, mutated = model.apply(variables, x, train=True, mutable=["batch_stats"])
    old = jax.tree.leaves(variables["batch_stats"])
    new = jax.tree.leaves(mutated["batch_stats"])
    assert any(not np.allclose(a, b) for a, b in zip(old, new))


def test_conv_init_statistics():
    """Kaiming fan-out: stem conv std ~ sqrt(2 / (3*3*64))."""
    model_fn, _ = MODEL_DICT["resnet18"]
    variables = model_fn().init(
        jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=False
    )
    k = np.asarray(variables["params"]["conv1"]["kernel"])  # (3,3,3,64)
    expected_std = np.sqrt(2.0 / (3 * 3 * 64))
    assert abs(k.std() - expected_std) / expected_std < 0.15


def test_linear_init_statistics():
    """torch Linear init: U(±1/sqrt(fan_in)) for kernel and bias."""
    cls = LinearClassifier(model_name="resnet50", num_classes=100)
    variables = cls.init(jax.random.key(0), jnp.zeros((2, 2048)))
    k = np.asarray(variables["params"]["fc"]["kernel"])
    bound = 1.0 / np.sqrt(2048)
    assert k.min() >= -bound and k.max() <= bound
    assert k.std() > bound / 3  # uniform, not degenerate


def test_remat_identical_numerics():
    """remat=True recomputes activations in backward but must not change the
    forward output or the gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from simclr_pytorch_distributed_tpu.models import SupConResNet

    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((4, 16, 16, 3)), jnp.float32
    )

    outs = {}
    for remat in (False, True):
        model = SupConResNet(model_name="resnet10", remat=remat)
        v = model.init(jax.random.key(0), jnp.zeros((2, 16, 16, 3)), train=False)

        def loss(params):
            feats, _ = model.apply(
                {"params": params, "batch_stats": v["batch_stats"]},
                x, train=True, mutable=["batch_stats"],
            )
            return jnp.sum(jnp.square(feats))

        val, grads = jax.value_and_grad(loss)(v["params"])
        outs[remat] = (float(val), grads)

    np.testing.assert_allclose(outs[True][0], outs[False][0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(outs[False][1]), jax.tree.leaves(outs[True][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
