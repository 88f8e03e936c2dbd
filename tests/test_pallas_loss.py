"""Fused Pallas contrastive loss vs the dense oracle (interpret mode on CPU).

The dense oracle ``ops.losses.supcon_loss`` is itself golden-tested against the
reference math in ``test_losses.py``; here the flash-style kernel must match it
(value and gradient) across methods, shapes that exercise multi-block grids,
and temperatures.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from simclr_pytorch_distributed_tpu.ops.losses import supcon_loss
from simclr_pytorch_distributed_tpu.ops.pallas_loss import (
    fused_sharded_supcon_loss,
    fused_supcon_loss,
    supports,
    supports_sharded,
)


def _features(rng, batch, n_views=2, dim=24):
    f = rng.standard_normal((batch, n_views, dim)).astype(np.float32)
    f /= np.linalg.norm(f, axis=-1, keepdims=True)
    return jnp.asarray(f)


@pytest.mark.parametrize("batch,dim", [(16, 24), (32, 128)])
@pytest.mark.parametrize("use_labels", [False, True])
@pytest.mark.parametrize("temp", [0.07, 0.5])
def test_fused_matches_dense(rng, batch, dim, use_labels, temp):
    f = _features(rng, batch, dim=dim)
    labels = (
        jnp.asarray(rng.integers(0, 5, batch).astype(np.int32))
        if use_labels
        else None
    )
    dense = supcon_loss(f, labels=labels, temperature=temp)
    fused = fused_supcon_loss(f, labels=labels, temperature=temp, interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(dense), rtol=2e-6)


@pytest.mark.parametrize("use_labels", [False, True])
def test_fused_gradient_matches_dense(rng, use_labels):
    batch = 16
    f = _features(rng, batch)
    labels = (
        jnp.asarray(rng.integers(0, 4, batch).astype(np.int32))
        if use_labels
        else None
    )
    gd = jax.grad(lambda x: supcon_loss(x, labels=labels, temperature=0.5))(f)
    gf = jax.grad(
        lambda x: fused_supcon_loss(
            x, labels=labels, temperature=0.5, interpret=True
        )
    )(f)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gd), atol=1e-6)


def test_multi_block_grid(rng):
    # V*B = 96 with small caps => 12x6 grid: online-LSE streaming across many
    # column blocks and several row programs.
    f = _features(rng, 48, dim=16)
    dense = supcon_loss(f, temperature=0.3)
    fused = fused_supcon_loss(
        f, temperature=0.3, interpret=True, block_rows=8, block_cols=16
    )
    np.testing.assert_allclose(np.asarray(fused), np.asarray(dense), rtol=2e-6)


def test_recipe_scale_ratio_preserved(rng):
    # the tau/tau_base=0.07 multiplier (reference losses.py:90) must carry over
    f = _features(rng, 8)
    a = fused_supcon_loss(f, temperature=0.5, interpret=True)
    b = fused_supcon_loss(
        f, temperature=0.5, base_temperature=0.5, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(a) / np.asarray(b), 0.5 / 0.07, rtol=1e-5
    )


def test_supports():
    assert supports(256, 2)  # the recipe: V*B = 512
    assert supports(4, 2)
    assert not supports(3, 1)  # N=3 not divisible by 8


# ---------------------------------------------------------------------------
# Sharded mode: the kernel inside shard_map over an 8-device mesh.
# ---------------------------------------------------------------------------


def _data_mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def _sharded_fn(mesh, labels, temp):
    """shard_map-wrapped sharded fused loss over view-major global rows."""
    if labels is None:
        return shard_map(
            lambda r: fused_sharded_supcon_loss(
                r, None, axis_name="data", temperature=temp, interpret=True
            ),
            mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False,
        )
    fn = shard_map(
        lambda r, l: fused_sharded_supcon_loss(
            r, l, axis_name="data", temperature=temp, interpret=True
        ),
        mesh=mesh, in_specs=(P("data"), P()), out_specs=P(), check_vma=False,
    )
    return lambda r: fn(r, labels)


@pytest.mark.parametrize("use_labels", [False, True])
@pytest.mark.parametrize("temp", [0.07, 0.5])
def test_sharded_fused_matches_dense(rng, use_labels, temp):
    """The shard_map-sharded kernel == dense on the 8-device mesh (value)."""
    batch = 32  # 64 view-major rows -> 8 anchor rows per device
    f = _features(rng, batch)
    labels = (
        jnp.asarray(rng.integers(0, 5, batch).astype(np.int32))
        if use_labels
        else None
    )
    rows = jnp.transpose(f, (1, 0, 2)).reshape(2 * batch, -1)
    dense = supcon_loss(f, labels=labels, temperature=temp)
    sharded = _sharded_fn(_data_mesh(), labels, temp)(rows)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense), rtol=2e-6)


@pytest.mark.parametrize("use_labels", [False, True])
def test_sharded_fused_gradient_matches_dense(rng, use_labels):
    """Each device's custom-VJP backward computes the exact global gradient of
    its own anchor rows (incl. the Gᵀ cross-device term via gathered lse/cnt)."""
    batch = 32
    f = _features(rng, batch)
    labels = (
        jnp.asarray(rng.integers(0, 4, batch).astype(np.int32))
        if use_labels
        else None
    )
    rows = jnp.transpose(f, (1, 0, 2)).reshape(2 * batch, -1)

    def dense_of_rows(r):
        return supcon_loss(
            jnp.stack([r[:batch], r[batch:]], axis=1),
            labels=labels, temperature=0.5,
        )

    gd = jax.grad(dense_of_rows)(rows)
    gs = jax.grad(_sharded_fn(_data_mesh(), labels, 0.5))(rows)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gd), atol=1e-6)


def test_supports_sharded():
    assert supports_sharded(256, 2, 8)  # the recipe on a v5e-8: m=64
    assert supports_sharded(4096, 2, 8)  # ImageNet-scale: m=1024
    assert not supports_sharded(16, 2, 8)  # m=4 < one 8-row tile
    assert not supports_sharded(20, 2, 8)  # 40 rows not divisible by 8
    assert not supports_sharded(256, 2, 0)


def test_unsupported_size_raises(rng):
    f = _features(rng, 3, n_views=1)
    with pytest.raises(ValueError):
        fused_supcon_loss(f, interpret=True)


def test_fused_train_step_single_device(rng):
    """make_train_step with loss_impl='fused' runs and matches the dense step."""
    import optax

    from simclr_pytorch_distributed_tpu.models import SupConResNet
    from simclr_pytorch_distributed_tpu.train.state import create_train_state
    from simclr_pytorch_distributed_tpu.train.supcon_step import (
        SupConStepConfig,
        make_train_step,
    )

    model = SupConResNet(model_name="resnet18", head="mlp", feat_dim=128)
    tx = optax.sgd(0.1, momentum=0.9)
    state = create_train_state(
        model, tx, jax.random.key(0), jnp.zeros((2, 16, 16, 3))
    )
    images = jnp.asarray(
        rng.standard_normal((8, 2, 16, 16, 3)).astype(np.float32)
    )
    labels = jnp.asarray(rng.integers(0, 4, 8).astype(np.int32))

    outs = {}
    for impl in ("dense", "fused"):
        cfg = SupConStepConfig(
            method="SimCLR", temperature=0.5, epochs=2, steps_per_epoch=1,
            grad_div=2.0, loss_impl=impl,
        )
        step = make_train_step(model, tx, lambda s: 0.1, cfg)
        new_state, metrics = step(state, images, labels)
        outs[impl] = (new_state, metrics)

    np.testing.assert_allclose(
        float(outs["fused"][1]["loss"]), float(outs["dense"][1]["loss"]),
        rtol=1e-5,
    )
    d_leaves = jax.tree.leaves(outs["dense"][0].params)
    f_leaves = jax.tree.leaves(outs["fused"][0].params)
    for a, b in zip(d_leaves, f_leaves):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5)
