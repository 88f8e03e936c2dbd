"""CrossReplicaBatchNorm numerics vs torch BatchNorm2d, and sync semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from simclr_pytorch_distributed_tpu.models.norm import (
    CrossReplicaBatchNorm,
    FusedTrainBN,
    running_stats_update,
)


def torch_bn_reference(x_nhwc, n_steps=1):
    """Run torch BatchNorm2d over the same data, return (y, running_mean, running_var)."""
    bn = torch.nn.BatchNorm2d(x_nhwc.shape[-1])
    bn.train()
    xt = torch.from_numpy(np.transpose(x_nhwc, (0, 3, 1, 2)))
    for _ in range(n_steps):
        y = bn(xt)
    return (
        np.transpose(y.detach().numpy(), (0, 2, 3, 1)),
        bn.running_mean.numpy(),
        bn.running_var.numpy(),
    )


def test_train_mode_matches_torch(rng):
    x = rng.normal(loc=1.5, scale=2.0, size=(8, 4, 4, 16)).astype(np.float32)
    bn = CrossReplicaBatchNorm()
    variables = bn.init(jax.random.key(0), jnp.asarray(x))
    y, mutated = bn.apply(variables, jnp.asarray(x), mutable=["batch_stats"])
    y_t, rm_t, rv_t = torch_bn_reference(x)
    np.testing.assert_allclose(np.asarray(y), y_t, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mutated["batch_stats"]["mean"]), rm_t, rtol=1e-5, atol=1e-6)
    # unbiased running var is the torch semantic being checked here
    np.testing.assert_allclose(np.asarray(mutated["batch_stats"]["var"]), rv_t, rtol=1e-4, atol=1e-5)


def test_eval_mode_uses_running_stats(rng):
    x = rng.normal(size=(4, 2, 2, 8)).astype(np.float32)
    bn = CrossReplicaBatchNorm(use_running_average=True)
    variables = bn.init(jax.random.key(0), jnp.asarray(x))
    y = bn.apply(variables, jnp.asarray(x))
    # fresh running stats are mean 0 var 1 -> output ~ input (eps-scaled)
    np.testing.assert_allclose(np.asarray(y), x / np.sqrt(1 + 1e-5), rtol=1e-5, atol=1e-6)


def test_grouped_bn_matches_independent_per_shard_bn(rng):
    """GSPMD per-device mode (sync=False, local_groups=G) == G INDEPENDENT
    whole-batch BNs, one per data-parallel slice — the reference's default
    per-GPU BatchNorm2d, expressible without per-device programs."""
    g, v, per = 4, 2, 3  # groups x views x images-per-group-per-view
    x = rng.normal(size=(v * g * per, 4, 4, 8)).astype(np.float32)
    # make the groups statistically distinct
    xv = x.reshape(v, g, per, 4, 4, 8)
    xv += np.arange(g, dtype=np.float32)[None, :, None, None, None, None] * 5.0
    x = xv.reshape(x.shape)

    bn_grouped = CrossReplicaBatchNorm(sync=False, local_groups=g, group_views=v)
    variables = bn_grouped.init(jax.random.key(0), jnp.asarray(x))
    y, mut = bn_grouped.apply(variables, jnp.asarray(x), mutable=["batch_stats"])
    y = np.asarray(y).reshape(v, g, per, 4, 4, 8)

    bn_one = CrossReplicaBatchNorm()
    for gi in range(g):
        # group gi = both views of batch-slice gi, exactly the reference's
        # per-GPU batch composition
        xg = xv[:, gi].reshape(v * per, 4, 4, 8)
        y_ref, mut_ref = bn_one.apply(
            bn_one.init(jax.random.key(0), jnp.asarray(xg)),
            jnp.asarray(xg), mutable=["batch_stats"],
        )
        np.testing.assert_allclose(
            y[:, gi].reshape(v * per, 4, 4, 8), np.asarray(y_ref),
            rtol=1e-4, atol=1e-5,
        )
        if gi == 0:
            # running stats track group 0 (DDP broadcast_buffers semantics)
            np.testing.assert_allclose(
                np.asarray(mut["batch_stats"]["mean"]),
                np.asarray(mut_ref["batch_stats"]["mean"]), rtol=1e-5, atol=1e-6,
            )
            np.testing.assert_allclose(
                np.asarray(mut["batch_stats"]["var"]),
                np.asarray(mut_ref["batch_stats"]["var"]), rtol=1e-4, atol=1e-5,
            )

    # and it differs from global-batch BN (the groups were made distinct)
    y_global = np.asarray(bn_one.apply(variables, jnp.asarray(x), mutable=["batch_stats"])[0])
    assert np.abs(y_global - y.reshape(y_global.shape)).max() > 0.5

    # indivisible batch fails loudly instead of silently regrouping
    with pytest.raises(ValueError, match="views"):
        bn_grouped.apply(variables, jnp.asarray(x[:10]), mutable=["batch_stats"])


def test_grouped_bn_init_with_tiny_example_batch():
    """init() traces with a 2-row example batch that cannot divide into the
    groups — the grouped branch must be inert during initialization (the
    driver's create_train_state would otherwise crash every multi-device
    sync-off run at startup)."""
    bn = CrossReplicaBatchNorm(sync=False, local_groups=8, group_views=2)
    variables = bn.init(jax.random.key(0), jnp.zeros((2, 4, 4, 3)))
    assert variables["batch_stats"]["mean"].shape == (3,)


def test_grouped_bn_identical_under_sharded_jit(rng):
    """The grouped math is layout-independent: jit over the 8-device mesh with
    the batch sharded on 'data' produces the same outputs and running stats."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    g = 8
    x = rng.normal(size=(g * 2 * 2, 2, 2, 4)).astype(np.float32)
    bn = CrossReplicaBatchNorm(sync=False, local_groups=g, group_views=2)
    variables = bn.init(jax.random.key(0), jnp.asarray(x))

    y_host, mut_host = bn.apply(variables, jnp.asarray(x), mutable=["batch_stats"])

    mesh = Mesh(np.array(jax.devices()), ("data",))
    xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data")))
    y_jit, mut_jit = jax.jit(
        lambda v, xx: bn.apply(v, xx, mutable=["batch_stats"])
    )(variables, xs)
    np.testing.assert_allclose(np.asarray(y_jit), np.asarray(y_host), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(mut_jit["batch_stats"]["var"]),
        np.asarray(mut_host["batch_stats"]["var"]), rtol=1e-4, atol=1e-5,
    )


def test_shard_map_sync_equals_full_batch(rng):
    """pmean-synced per-device BN == BN over the concatenated batch — the
    SyncBatchNorm semantic (reference main_supcon.py:223-224) mesh-natively."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devices = jax.devices()
    assert len(devices) == 8, "conftest must fake 8 CPU devices"
    x = rng.normal(loc=0.5, size=(16, 4, 4, 8)).astype(np.float32)

    bn_sync = CrossReplicaBatchNorm(axis_name="data")
    bn_full = CrossReplicaBatchNorm()
    variables = bn_full.init(jax.random.key(0), jnp.asarray(x))

    mesh = Mesh(np.array(devices), ("data",))

    def per_device(xs):
        y, mut = bn_sync.apply(variables, xs, mutable=["batch_stats"])
        return y, mut["batch_stats"]["mean"], mut["batch_stats"]["var"]

    y_sharded, rm, rv = shard_map(
        per_device,
        mesh=mesh,
        in_specs=P("data"),
        out_specs=(P("data"), P(), P()),
    )(jnp.asarray(x))

    y_full, mut_full = bn_full.apply(variables, jnp.asarray(x), mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(y_sharded), np.asarray(y_full), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(rm), np.asarray(mut_full["batch_stats"]["mean"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(rv), np.asarray(mut_full["batch_stats"]["var"]), rtol=1e-4, atol=1e-5)


def test_unsynced_bn_uses_local_stats(rng):
    """sync=False reproduces the reference's non---syncBN per-device BN."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    x = rng.normal(loc=0.0, scale=1.0, size=(16, 2, 2, 4)).astype(np.float32)
    # make shards statistically distinct
    x[:8] += 10.0

    bn_local = CrossReplicaBatchNorm(axis_name="data", sync=False)
    variables = bn_local.init(jax.random.key(0), jnp.asarray(x))
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    y = shard_map(
        lambda xs: bn_local.apply(variables, xs, mutable=["batch_stats"])[0],
        mesh=mesh,
        in_specs=P("data"),
        out_specs=P("data"),
    )(jnp.asarray(x))

    # local normalization: each half is zero-mean on its own
    y = np.asarray(y)
    assert abs(y[:8].mean()) < 1e-4 and abs(y[8:].mean()) < 1e-4

    # whereas synced normalization would leave the halves offset
    bn_sync = CrossReplicaBatchNorm(axis_name="data")
    y_s = shard_map(
        lambda xs: bn_sync.apply(variables, xs, mutable=["batch_stats"])[0],
        mesh=mesh,
        in_specs=P("data"),
        out_specs=P("data"),
    )(jnp.asarray(x))
    y_s = np.asarray(y_s)
    assert y_s[:8].mean() > 0.5 and y_s[8:].mean() < -0.5


def test_fused_train_bn_running_update_matches_norm():
    """FusedTrainBN's second call applies EXACTLY the norm.py running
    update (single-sourced via running_stats_update)."""
    bn = FusedTrainBN(4)
    v = bn.init(jax.random.key(0))
    m = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    var = jnp.asarray([0.5, 1.5, 2.5, 3.5])
    (scale, bias), mut = bn.apply(v, m, var, 100, mutable=["batch_stats"])
    exp_m, exp_v = running_stats_update(
        jnp.zeros((4,)), jnp.ones((4,)), m, var, 100, 0.1
    )
    np.testing.assert_allclose(np.asarray(mut["batch_stats"]["mean"]), exp_m)
    np.testing.assert_allclose(np.asarray(mut["batch_stats"]["var"]), exp_v)
    np.testing.assert_array_equal(np.asarray(scale), np.ones(4))
    np.testing.assert_array_equal(np.asarray(bias), np.zeros(4))
