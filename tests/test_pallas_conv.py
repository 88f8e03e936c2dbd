"""Fused Pallas conv+BN+ReLU kernels vs the Flax oracle (interpret mode).

The fused stem (``fused_conv_bn_relu``) and residual-block kernels
(``fused_basic_block`` / ``fused_projection_block`` /
``fused_bottleneck_block``, ops/pallas_conv.py) must match the
bitwise-pinned Flax path — ``nn.Conv`` + ``CrossReplicaBatchNorm`` in
whole-batch train mode — in value, in every parameter/input gradient, and
in the batch statistics that feed the running-stat update, across every
geometry class ``supports_*`` admits. bf16 kernel variants compare
against the SAME fp32 Flax reference at the round-19 derived tolerances
(docs/PERF.md round 19). Unsupported geometries and dtypes must fall back
to the XLA path, eval mode must stay bitwise-XLA, and the param tree must
be impl-independent (a ``--conv_impl pallas`` checkpoint restores under
``--conv_impl xla`` — proven through the real driver below).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from simclr_pytorch_distributed_tpu import config as config_lib
from simclr_pytorch_distributed_tpu.models import SupConResNet
from simclr_pytorch_distributed_tpu.models.norm import (
    CrossReplicaBatchNorm,
    FusedTrainBN,
    running_stats_update,
)
from simclr_pytorch_distributed_tpu.models.resnet import (
    BasicBlock,
    Bottleneck,
    fused_site_plan,
)
from simclr_pytorch_distributed_tpu.ops import pallas_conv

pytestmark = pytest.mark.kernel

# Interpret-mode kernels accumulate in a different order than XLA's conv
# emitter; fp32 accumulation noise at these magnitudes measured ~1e-6
# relative (values) / ~3e-5 absolute on O(100) gradient scales. Pinned
# with ~30x margin.
VAL_RTOL, VAL_ATOL = 3e-5, 3e-5
GRAD_RTOL, GRAD_ATOL = 1e-4, 1e-3

# bf16 kernels vs the fp32 Flax reference: bf16 unit roundoff is
# 2^-8 ~= 3.9e-3; measured worst cases across all kinds/geometries were
# value scaled-maxabs 5.9e-3 (~1.5 ulp) and grad cosine 0.9905 — ReLU
# masks flip for pre-activations within roundoff of zero, which spikes
# per-entry grad diffs while leaving the gradient DIRECTION intact, so
# grads bind on cosine with a loose scaled-maxabs sanity bound. Pinned at
# ~3-5x margin (full derivation: docs/PERF.md round 19).
BF16_VAL_SCALED, BF16_VAL_COS = 2e-2, 0.9999
BF16_GRAD_COS, BF16_GRAD_SCALED = 0.95, 0.5
BF16_STATS_SCALED = 2e-2


def _assert_close_bf16(a, b, *, kind, name=""):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    scaled = float(np.max(np.abs(a - b))) / (float(np.max(np.abs(b))) + 1e-30)
    if kind == "stats":
        assert scaled <= BF16_STATS_SCALED, (name, scaled)
        return
    av, bv = a.astype(np.float64).ravel(), b.astype(np.float64).ravel()
    cos = float(np.dot(av, bv)
                / (np.linalg.norm(av) * np.linalg.norm(bv) + 1e-30))
    if kind == "value":
        assert scaled <= BF16_VAL_SCALED and cos >= BF16_VAL_COS, (
            name, scaled, cos)
    else:
        assert cos >= BF16_GRAD_COS and scaled <= BF16_GRAD_SCALED, (
            name, scaled, cos)


def _flax_stem(x, k, g, b):
    """conv3x3/s1 + whole-batch train BN + ReLU via the production
    modules, returning (out, mutated batch_stats)."""

    class Stem(nn.Module):
        @nn.compact
        def __call__(self, xin):
            y = nn.Conv(
                k.shape[3], (3, 3), strides=(1, 1), use_bias=False,
                padding=((1, 1), (1, 1)), param_dtype=jnp.float32,
                name="conv",
            )(xin)
            return nn.relu(
                CrossReplicaBatchNorm(use_running_average=False, name="bn")(y)
            )

    mod = Stem()
    variables = {
        "params": {
            "conv": {"kernel": k},
            "bn": {"scale": g, "bias": b},
        },
        "batch_stats": {
            "bn": {
                "mean": jnp.zeros((k.shape[3],), jnp.float32),
                "var": jnp.ones((k.shape[3],), jnp.float32),
            }
        },
    }
    return mod.apply(variables, x, mutable=["batch_stats"])


def _flax_block(x, k1, g1, b1, k2, g2, b2):
    """The production BasicBlock (identity shortcut) in train mode."""
    mod = BasicBlock(planes=k1.shape[3])
    variables = {
        "params": {
            "Conv_0": {"kernel": k1},
            "bn1": {"scale": g1, "bias": b1},
            "Conv_1": {"kernel": k2},
            "bn2": {"scale": g2, "bias": b2},
        },
        "batch_stats": {
            "bn1": {
                "mean": jnp.zeros((k1.shape[3],), jnp.float32),
                "var": jnp.ones((k1.shape[3],), jnp.float32),
            },
            "bn2": {
                "mean": jnp.zeros((k2.shape[3],), jnp.float32),
                "var": jnp.ones((k2.shape[3],), jnp.float32),
            },
        },
    }
    return mod.apply(variables, x, True, mutable=["batch_stats"])


def _block_args(rng, n, h, w, c):
    def arr(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32) * scale + shift
        )

    return (
        arr(n, h, w, c),
        arr(3, 3, c, c, scale=0.2), arr(c, shift=1.0), arr(c, scale=0.1),
        arr(3, 3, c, c, scale=0.2), arr(c, shift=1.0), arr(c, scale=0.1),
    )


# one geometry per admitted class: square stage-1-like, non-square (h != w),
# tall-channel, and a batch the tile picker must split unevenly (bn=4)
BLOCK_GEOMETRIES = [(16, 8, 8, 8), (8, 10, 6, 16), (16, 4, 4, 24), (12, 8, 8, 8)]


@pytest.mark.parametrize("n,h,w,c", BLOCK_GEOMETRIES)
def test_fused_block_forward_matches_flax(rng, n, h, w, c):
    x, k1, g1, b1, k2, g2, b2 = _block_args(rng, n, h, w, c)
    assert pallas_conv.supports_block(n, h, w, c)
    out_f, m1, v1, m2, v2 = pallas_conv.fused_basic_block(
        x, k1, g1, b1, k2, g2, b2, interpret=True
    )
    out_r, mut = _flax_block(x, k1, g1, b1, k2, g2, b2)
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_r), rtol=VAL_RTOL, atol=VAL_ATOL
    )
    # batch moments -> the same running-stat update as models/norm.py
    count = n * h * w
    for bn_name, (m, v) in (("bn1", (m1, v1)), ("bn2", (m2, v2))):
        ra_m, ra_v = running_stats_update(
            jnp.zeros((c,)), jnp.ones((c,)), m, v, count, 0.1
        )
        np.testing.assert_allclose(
            np.asarray(ra_m),
            np.asarray(mut["batch_stats"][bn_name]["mean"]),
            rtol=VAL_RTOL, atol=VAL_ATOL,
        )
        np.testing.assert_allclose(
            np.asarray(ra_v),
            np.asarray(mut["batch_stats"][bn_name]["var"]),
            rtol=VAL_RTOL, atol=VAL_ATOL,
        )


@pytest.mark.parametrize("n,h,w,c", BLOCK_GEOMETRIES[:2])
def test_fused_block_gradients_match_flax(rng, n, h, w, c):
    args = _block_args(rng, n, h, w, c)

    def loss_fused(*a):
        out = pallas_conv.fused_basic_block(*a, interpret=True)[0]
        return jnp.sum(out * jnp.cos(out))

    def loss_flax(*a):
        out, _ = _flax_block(*a)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_fused, argnums=tuple(range(7)))(*args)
    gr = jax.grad(loss_flax, argnums=tuple(range(7)))(*args)
    names = ("dx", "dk1", "dg1", "db1", "dk2", "dg2", "db2")
    for name, a, b in zip(names, gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=GRAD_RTOL, atol=GRAD_ATOL,
            err_msg=name,
        )


def test_fused_stem_matches_flax_value_and_grads(rng):
    n, h, w, cin, cout = 8, 8, 8, 3, 16
    x = jnp.asarray(rng.standard_normal((n, h, w, cin)).astype(np.float32))
    k = jnp.asarray(
        rng.standard_normal((3, 3, cin, cout)).astype(np.float32) * 0.2
    )
    g = jnp.asarray(rng.standard_normal((cout,)).astype(np.float32) + 1.0)
    b = jnp.asarray(rng.standard_normal((cout,)).astype(np.float32) * 0.1)
    assert pallas_conv.supports_stem(n, h, w, cin, cout)

    out_f, m, v = pallas_conv.fused_conv_bn_relu(x, k, g, b, interpret=True)
    out_r, mut = _flax_stem(x, k, g, b)
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_r), rtol=VAL_RTOL, atol=VAL_ATOL
    )
    ra_m, ra_v = running_stats_update(
        jnp.zeros((cout,)), jnp.ones((cout,)), m, v, n * h * w, 0.1
    )
    np.testing.assert_allclose(
        np.asarray(ra_m), np.asarray(mut["batch_stats"]["bn"]["mean"]),
        rtol=VAL_RTOL, atol=VAL_ATOL,
    )
    np.testing.assert_allclose(
        np.asarray(ra_v), np.asarray(mut["batch_stats"]["bn"]["var"]),
        rtol=VAL_RTOL, atol=VAL_ATOL,
    )

    def loss_fused(*a):
        out, _, _ = pallas_conv.fused_conv_bn_relu(*a, interpret=True)
        return jnp.sum(out * jnp.cos(out))

    def loss_flax(*a):
        out, _ = _flax_stem(*a)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, k, g, b)
    gr = jax.grad(loss_flax, argnums=(0, 1, 2, 3))(x, k, g, b)
    for name, a, bb in zip(("dx", "dk", "dg", "db"), gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(bb), rtol=GRAD_RTOL, atol=GRAD_ATOL,
            err_msg=name,
        )


# ------------------------------------- projection / Bottleneck / bf16


def _flax_proj_block(x, k1, g1, b1, k2, g2, b2, ks, gs, bs, stride):
    """The production BasicBlock with the 1x1-conv+BN projection shortcut
    in train mode."""
    c = k1.shape[3]
    mod = BasicBlock(planes=c, stride=stride)
    variables = {
        "params": {
            "Conv_0": {"kernel": k1}, "bn1": {"scale": g1, "bias": b1},
            "Conv_1": {"kernel": k2}, "bn2": {"scale": g2, "bias": b2},
            "shortcut_conv": {"kernel": ks},
            "shortcut_bn": {"scale": gs, "bias": bs},
        },
        "batch_stats": {
            bn: {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
            for bn in ("bn1", "bn2", "shortcut_bn")
        },
    }
    return mod.apply(variables, x, True, mutable=["batch_stats"])


def _proj_args(rng, n, h, w, cin, c):
    def arr(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32) * scale + shift
        )

    return (
        arr(n, h, w, cin),
        arr(3, 3, cin, c, scale=0.2), arr(c, shift=1.0), arr(c, scale=0.1),
        arr(3, 3, c, c, scale=0.2), arr(c, shift=1.0), arr(c, scale=0.1),
        arr(1, 1, cin, c, scale=0.3), arr(c, shift=1.0), arr(c, scale=0.1),
    )


# stride-2 square, stride-1 channel-change, stride-2 non-square (h != w:
# the even-dims requirement is per-axis), uneven batch tile
PROJ_GEOMETRIES = [
    (16, 8, 8, 8, 16, 2), (8, 6, 6, 8, 24, 1), (8, 10, 6, 16, 16, 2),
    (12, 8, 8, 8, 16, 2),
]


@pytest.mark.parametrize("n,h,w,cin,c,stride", PROJ_GEOMETRIES)
def test_fused_projection_block_matches_flax(rng, n, h, w, cin, c, stride):
    args = _proj_args(rng, n, h, w, cin, c)
    assert pallas_conv.supports_block(n, h, w, c, stride=stride,
                                      in_channels=cin)
    out_f, m1, v1, m2, v2, mS, vS = pallas_conv.fused_projection_block(
        *args, stride=stride, interpret=True
    )
    out_r, mut = _flax_proj_block(*args, stride=stride)
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_r), rtol=VAL_RTOL, atol=VAL_ATOL
    )
    # all three BNs normalize over the block's OUTPUT grid
    count = n * (h // stride) * (w // stride)
    for bn_name, (m, v) in (
        ("bn1", (m1, v1)), ("bn2", (m2, v2)), ("shortcut_bn", (mS, vS))
    ):
        ra_m, ra_v = running_stats_update(
            jnp.zeros((c,)), jnp.ones((c,)), m, v, count, 0.1
        )
        np.testing.assert_allclose(
            np.asarray(ra_m),
            np.asarray(mut["batch_stats"][bn_name]["mean"]),
            rtol=VAL_RTOL, atol=VAL_ATOL, err_msg=bn_name,
        )
        np.testing.assert_allclose(
            np.asarray(ra_v),
            np.asarray(mut["batch_stats"][bn_name]["var"]),
            rtol=VAL_RTOL, atol=VAL_ATOL, err_msg=bn_name,
        )


@pytest.mark.parametrize("n,h,w,cin,c,stride", PROJ_GEOMETRIES[:2])
def test_fused_projection_block_gradients_match_flax(
    rng, n, h, w, cin, c, stride
):
    args = _proj_args(rng, n, h, w, cin, c)
    argnums = tuple(range(10))

    def loss_fused(*a):
        out = pallas_conv.fused_projection_block(
            *a, stride=stride, interpret=True
        )[0]
        return jnp.sum(out * jnp.cos(out))

    def loss_flax(*a):
        out, _ = _flax_proj_block(*a, stride=stride)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_fused, argnums=argnums)(*args)
    gr = jax.grad(loss_flax, argnums=argnums)(*args)
    names = ("dx", "dk1", "dg1", "db1", "dk2", "dg2", "db2",
             "dks", "dgs", "dbs")
    for name, a, b in zip(names, gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=GRAD_RTOL, atol=GRAD_ATOL,
            err_msg=name,
        )


def _flax_bottleneck(x, k1, g1, b1, k2, g2, b2, k3, g3, b3, shortcut,
                     stride):
    """The production Bottleneck (expansion 4) in train mode; ``shortcut``
    is (ks, gs, bs) for projection sites, None for identity."""
    pln = k1.shape[3]
    c4 = 4 * pln
    mod = Bottleneck(planes=pln, stride=stride)
    params = {
        "Conv_0": {"kernel": k1}, "bn1": {"scale": g1, "bias": b1},
        "Conv_1": {"kernel": k2}, "bn2": {"scale": g2, "bias": b2},
        "Conv_2": {"kernel": k3}, "bn3": {"scale": g3, "bias": b3},
    }
    stats = {
        "bn1": {"mean": jnp.zeros((pln,)), "var": jnp.ones((pln,))},
        "bn2": {"mean": jnp.zeros((pln,)), "var": jnp.ones((pln,))},
        "bn3": {"mean": jnp.zeros((c4,)), "var": jnp.ones((c4,))},
    }
    if shortcut is not None:
        ks, gs, bs = shortcut
        params["shortcut_conv"] = {"kernel": ks}
        params["shortcut_bn"] = {"scale": gs, "bias": bs}
        stats["shortcut_bn"] = {
            "mean": jnp.zeros((c4,)), "var": jnp.ones((c4,))
        }
    return mod.apply(
        {"params": params, "batch_stats": stats}, x, True,
        mutable=["batch_stats"],
    )


def _bottleneck_args(rng, n, h, w, cin, planes, proj):
    def arr(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32) * scale + shift
        )

    c4 = 4 * planes
    args = (
        arr(n, h, w, cin),
        arr(1, 1, cin, planes, scale=0.3),
        arr(planes, shift=1.0), arr(planes, scale=0.1),
        arr(3, 3, planes, planes, scale=0.2),
        arr(planes, shift=1.0), arr(planes, scale=0.1),
        arr(1, 1, planes, c4, scale=0.3),
        arr(c4, shift=1.0), arr(c4, scale=0.1),
    )
    if proj:
        args += (arr(1, 1, cin, c4, scale=0.3),
                 arr(c4, shift=1.0), arr(c4, scale=0.1))
    return args


# identity (in == 4*planes, stride 1), stride-2 projection, stride-1
# channel-change projection on a non-square grid
BOTTLENECK_GEOMETRIES = [
    (8, 8, 8, 32, 8, 1), (8, 8, 8, 16, 8, 2), (8, 10, 6, 16, 8, 1),
]


@pytest.mark.parametrize("n,h,w,cin,planes,stride", BOTTLENECK_GEOMETRIES)
def test_fused_bottleneck_block_matches_flax(
    rng, n, h, w, cin, planes, stride
):
    c4 = 4 * planes
    proj = stride != 1 or cin != c4
    args = _bottleneck_args(rng, n, h, w, cin, planes, proj)
    assert pallas_conv.supports_bottleneck(
        n, h, w, planes, stride=stride, in_channels=cin
    )
    sc = args[10:] if proj else None
    r = pallas_conv.fused_bottleneck_block(
        *args[:10], sc, stride=stride, interpret=True
    )
    out_r, mut = _flax_bottleneck(*args[:10], sc, stride=stride)
    np.testing.assert_allclose(
        np.asarray(r[0]), np.asarray(out_r), rtol=VAL_RTOL, atol=VAL_ATOL
    )
    # bn1 reduces over the INPUT grid (the 1x1 runs pre-stride);
    # bn2/bn3/shortcut_bn over the strided output grid
    count1 = n * h * w
    count2 = n * (h // stride) * (w // stride)
    moments = [("bn1", r[1], r[2], planes, count1),
               ("bn2", r[3], r[4], planes, count2),
               ("bn3", r[5], r[6], c4, count2)]
    if proj:
        moments.append(("shortcut_bn", r[7], r[8], c4, count2))
    for bn_name, m, v, cc, count in moments:
        ra_m, ra_v = running_stats_update(
            jnp.zeros((cc,)), jnp.ones((cc,)), m, v, count, 0.1
        )
        np.testing.assert_allclose(
            np.asarray(ra_m),
            np.asarray(mut["batch_stats"][bn_name]["mean"]),
            rtol=VAL_RTOL, atol=VAL_ATOL, err_msg=bn_name,
        )
        np.testing.assert_allclose(
            np.asarray(ra_v),
            np.asarray(mut["batch_stats"][bn_name]["var"]),
            rtol=VAL_RTOL, atol=VAL_ATOL, err_msg=bn_name,
        )


@pytest.mark.parametrize("n,h,w,cin,planes,stride", BOTTLENECK_GEOMETRIES[:2])
def test_fused_bottleneck_block_gradients_match_flax(
    rng, n, h, w, cin, planes, stride
):
    c4 = 4 * planes
    proj = stride != 1 or cin != c4
    args = _bottleneck_args(rng, n, h, w, cin, planes, proj)
    argnums = tuple(range(len(args)))

    def loss_fused(*a):
        sc = a[10:] if proj else None
        out = pallas_conv.fused_bottleneck_block(
            *a[:10], sc, stride=stride, interpret=True
        )[0]
        return jnp.sum(out * jnp.cos(out))

    def loss_flax(*a):
        sc = a[10:] if proj else None
        out, _ = _flax_bottleneck(*a[:10], sc, stride=stride)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_fused, argnums=argnums)(*args)
    gr = jax.grad(loss_flax, argnums=argnums)(*args)
    names = ["dx", "dk1", "dg1", "db1", "dk2", "dg2", "db2",
             "dk3", "dg3", "db3"]
    if proj:
        names += ["dks", "dgs", "dbs"]
    for name, a, b in zip(names, gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=GRAD_RTOL, atol=GRAD_ATOL,
            err_msg=name,
        )


def test_fused_stem_bf16_matches_fp32_reference(rng):
    """The bf16 stem kernel vs the fp32 Flax reference at the derived
    tolerances: MXU matmuls take bf16 inputs but accumulate fp32, and the
    BN statistics stay fp32 — so agreement is bf16-roundoff-bounded, not
    bitwise."""
    n, h, w, cin, cout = 16, 8, 8, 8, 16
    x = jnp.asarray(rng.standard_normal((n, h, w, cin)).astype(np.float32))
    k = jnp.asarray(
        rng.standard_normal((3, 3, cin, cout)).astype(np.float32) * 0.2
    )
    g = jnp.asarray(rng.standard_normal((cout,)).astype(np.float32) + 1.0)
    b = jnp.asarray(rng.standard_normal((cout,)).astype(np.float32) * 0.1)
    assert pallas_conv.supports_stem(n, h, w, cin, cout, dtype=jnp.bfloat16)

    xb = x.astype(jnp.bfloat16)
    out_f, m, v = pallas_conv.fused_conv_bn_relu(xb, k, g, b, interpret=True)
    assert out_f.dtype == jnp.bfloat16
    # BN moments accumulate and emit fp32 regardless of compute dtype
    assert m.dtype == jnp.float32 and v.dtype == jnp.float32
    out_r, mut = _flax_stem(x, k, g, b)
    _assert_close_bf16(out_f, out_r, kind="value", name="out")
    ra_m, ra_v = running_stats_update(
        jnp.zeros((cout,)), jnp.ones((cout,)), m, v, n * h * w, 0.1
    )
    _assert_close_bf16(ra_m, mut["batch_stats"]["bn"]["mean"],
                       kind="stats", name="mean")
    _assert_close_bf16(ra_v, mut["batch_stats"]["bn"]["var"],
                       kind="stats", name="var")

    def loss_fused(*a):
        out, _, _ = pallas_conv.fused_conv_bn_relu(
            a[0].astype(jnp.bfloat16), *a[1:], interpret=True
        )
        return jnp.sum(out.astype(jnp.float32) * jnp.cos(out))

    def loss_flax(*a):
        out, _ = _flax_stem(*a)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, k, g, b)
    gr = jax.grad(loss_flax, argnums=(0, 1, 2, 3))(x, k, g, b)
    for name, a, bb in zip(("dx", "dk", "dg", "db"), gf, gr):
        _assert_close_bf16(a, bb, kind="grad", name=name)


@pytest.mark.parametrize("n,h,w,c", [(16, 8, 8, 8), (8, 10, 6, 16)])
def test_fused_basic_block_bf16_matches_fp32_reference(rng, n, h, w, c):
    args = _block_args(rng, n, h, w, c)
    assert pallas_conv.supports_block(n, h, w, c, dtype=jnp.bfloat16)
    r = pallas_conv.fused_basic_block(
        args[0].astype(jnp.bfloat16), *args[1:], interpret=True
    )
    assert r[0].dtype == jnp.bfloat16
    out_r, mut = _flax_block(*args)
    _assert_close_bf16(r[0], out_r, kind="value", name="out")
    count = n * h * w
    for bn_name, (m, v) in (("bn1", (r[1], r[2])), ("bn2", (r[3], r[4]))):
        ra_m, ra_v = running_stats_update(
            jnp.zeros((c,)), jnp.ones((c,)), m, v, count, 0.1
        )
        _assert_close_bf16(ra_m, mut["batch_stats"][bn_name]["mean"],
                           kind="stats", name=bn_name)
        _assert_close_bf16(ra_v, mut["batch_stats"][bn_name]["var"],
                           kind="stats", name=bn_name)

    def loss_fused(*a):
        out = pallas_conv.fused_basic_block(
            a[0].astype(jnp.bfloat16), *a[1:], interpret=True
        )[0]
        return jnp.sum(out.astype(jnp.float32) * jnp.cos(out))

    def loss_flax(*a):
        out, _ = _flax_block(*a)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_fused, argnums=tuple(range(7)))(*args)
    gr = jax.grad(loss_flax, argnums=tuple(range(7)))(*args)
    for name, a, b in zip(
        ("dx", "dk1", "dg1", "db1", "dk2", "dg2", "db2"), gf, gr
    ):
        _assert_close_bf16(a, b, kind="grad", name=name)


@pytest.mark.parametrize("n,h,w,cin,c,stride",
                         [(16, 8, 8, 8, 16, 2), (8, 6, 6, 8, 24, 1)])
def test_fused_projection_block_bf16_matches_fp32_reference(
    rng, n, h, w, cin, c, stride
):
    args = _proj_args(rng, n, h, w, cin, c)
    assert pallas_conv.supports_block(
        n, h, w, c, stride=stride, in_channels=cin, dtype=jnp.bfloat16
    )
    r = pallas_conv.fused_projection_block(
        args[0].astype(jnp.bfloat16), *args[1:], stride=stride,
        interpret=True,
    )
    out_r, mut = _flax_proj_block(*args, stride=stride)
    _assert_close_bf16(r[0], out_r, kind="value", name="out")
    count = n * (h // stride) * (w // stride)
    for bn_name, (m, v) in (
        ("bn1", (r[1], r[2])), ("bn2", (r[3], r[4])),
        ("shortcut_bn", (r[5], r[6])),
    ):
        ra_m, ra_v = running_stats_update(
            jnp.zeros((c,)), jnp.ones((c,)), m, v, count, 0.1
        )
        _assert_close_bf16(ra_m, mut["batch_stats"][bn_name]["mean"],
                           kind="stats", name=bn_name)
        _assert_close_bf16(ra_v, mut["batch_stats"][bn_name]["var"],
                           kind="stats", name=bn_name)

    argnums = tuple(range(10))

    def loss_fused(*a):
        out = pallas_conv.fused_projection_block(
            a[0].astype(jnp.bfloat16), *a[1:], stride=stride, interpret=True
        )[0]
        return jnp.sum(out.astype(jnp.float32) * jnp.cos(out))

    def loss_flax(*a):
        out, _ = _flax_proj_block(*a, stride=stride)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_fused, argnums=argnums)(*args)
    gr = jax.grad(loss_flax, argnums=argnums)(*args)
    names = ("dx", "dk1", "dg1", "db1", "dk2", "dg2", "db2",
             "dks", "dgs", "dbs")
    for name, a, b in zip(names, gf, gr):
        _assert_close_bf16(a, b, kind="grad", name=name)


@pytest.mark.parametrize("n,h,w,cin,planes,stride",
                         [(8, 8, 8, 32, 8, 1), (8, 8, 8, 16, 8, 2)])
def test_fused_bottleneck_block_bf16_matches_fp32_reference(
    rng, n, h, w, cin, planes, stride
):
    c4 = 4 * planes
    proj = stride != 1 or cin != c4
    args = _bottleneck_args(rng, n, h, w, cin, planes, proj)
    assert pallas_conv.supports_bottleneck(
        n, h, w, planes, stride=stride, in_channels=cin, dtype=jnp.bfloat16
    )
    sc = args[10:] if proj else None
    r = pallas_conv.fused_bottleneck_block(
        args[0].astype(jnp.bfloat16), *args[1:10], sc, stride=stride,
        interpret=True,
    )
    out_r, mut = _flax_bottleneck(*args[:10], sc, stride=stride)
    _assert_close_bf16(r[0], out_r, kind="value", name="out")
    count1 = n * h * w
    count2 = n * (h // stride) * (w // stride)
    moments = [("bn1", r[1], r[2], planes, count1),
               ("bn2", r[3], r[4], planes, count2),
               ("bn3", r[5], r[6], c4, count2)]
    if proj:
        moments.append(("shortcut_bn", r[7], r[8], c4, count2))
    for bn_name, m, v, cc, count in moments:
        ra_m, ra_v = running_stats_update(
            jnp.zeros((cc,)), jnp.ones((cc,)), m, v, count, 0.1
        )
        _assert_close_bf16(ra_m, mut["batch_stats"][bn_name]["mean"],
                           kind="stats", name=bn_name)
        _assert_close_bf16(ra_v, mut["batch_stats"][bn_name]["var"],
                           kind="stats", name=bn_name)

    argnums = tuple(range(len(args)))

    def loss_fused(*a):
        sc = a[10:] if proj else None
        out = pallas_conv.fused_bottleneck_block(
            a[0].astype(jnp.bfloat16), *a[1:10], sc, stride=stride,
            interpret=True,
        )[0]
        return jnp.sum(out.astype(jnp.float32) * jnp.cos(out))

    def loss_flax(*a):
        sc = a[10:] if proj else None
        out, _ = _flax_bottleneck(*a[:10], sc, stride=stride)
        return jnp.sum(out * jnp.cos(out))

    gf = jax.grad(loss_fused, argnums=argnums)(*args)
    gr = jax.grad(loss_flax, argnums=argnums)(*args)
    names = ["dx", "dk1", "dg1", "db1", "dk2", "dg2", "db2",
             "dk3", "dg3", "db3"]
    if proj:
        names += ["dks", "dgs", "dbs"]
    for name, a, b in zip(names, gf, gr):
        _assert_close_bf16(a, b, kind="grad", name=name)


def test_supports_gates():
    # stride-2 / channel-changing sites are admitted since round 19 (the
    # projection-shortcut kernel) — the round-15 inversions, inverted
    assert pallas_conv.supports_block(16, 8, 8, 16, stride=2, in_channels=8)
    assert pallas_conv.supports_block(16, 8, 8, 16, in_channels=8)
    # ... but stride 2 requires EVEN input dims (the dilated
    # transposed-conv backward assumes ho == h // 2 exactly), per axis
    assert not pallas_conv.supports_block(16, 9, 8, 16, stride=2,
                                          in_channels=8)
    assert not pallas_conv.supports_block(16, 8, 9, 16, stride=2,
                                          in_channels=8)
    # stride-1 odd dims stay admitted (no such constraint)
    assert pallas_conv.supports_block(8, 9, 9, 8)
    # degenerate spatial dims (3x3 window needs h,w >= 3)
    assert not pallas_conv.supports_block(16, 2, 2, 8)
    # VMEM blowout: stage-4-like 512 channels (weights + dW accumulators
    # alone exceed the budget)
    assert not pallas_conv.supports_block(8, 16, 16, 512)
    # admitted classes
    assert pallas_conv.supports_block(512, 32, 32, 64)   # rn18 stage 1 @ B=256
    assert pallas_conv.supports_block(512, 16, 16, 128)  # rn18 stage 2 @ B=256
    assert pallas_conv.supports_stem(512, 32, 32, 3, 64)
    # Bottleneck gate: rn50 stage-1 identity and stage-leading projection
    assert pallas_conv.supports_bottleneck(512, 32, 32, 64, in_channels=256)
    assert pallas_conv.supports_bottleneck(
        512, 32, 32, 64, stride=1, in_channels=64  # layer1_block0
    )
    assert not pallas_conv.supports_bottleneck(
        512, 33, 32, 64, stride=2, in_channels=64  # odd dim at stride 2
    )
    assert not pallas_conv.supports_bottleneck(
        512, 32, 32, 128, stride=2, in_channels=256  # VMEM: rn50 layer2_block0
    )
    # compute dtype is part of the admission key: bf16 halves the VMEM
    # footprint, admitting sites fp32 rejects...
    assert not pallas_conv.supports_block(
        512, 16, 16, 256, stride=2, in_channels=128
    )
    assert pallas_conv.supports_block(
        512, 16, 16, 256, stride=2, in_channels=128, dtype=jnp.bfloat16
    )
    # ...and any dtype outside {fp32, bf16} is rejected outright
    assert not pallas_conv.supports_block(16, 8, 8, 8, dtype=jnp.float16)
    assert not pallas_conv.supports_stem(16, 8, 8, 3, 16, dtype=jnp.float16)
    assert not pallas_conv.supports_bottleneck(
        16, 8, 8, 8, in_channels=32, dtype=jnp.float16
    )


def test_direct_call_rejects_inadmissible_geometry():
    with pytest.raises(ValueError, match="supports_block"):
        # stride/in_channels admissible but VMEM-inadmissible channels
        pallas_conv.fused_basic_block(
            jnp.zeros((8, 16, 16, 512)), jnp.zeros((3, 3, 512, 512)),
            jnp.ones((512,)), jnp.zeros((512,)),
            jnp.zeros((3, 3, 512, 512)), jnp.ones((512,)),
            jnp.zeros((512,)), interpret=True,
        )
    c = 8
    proj_args = (
        jnp.zeros((8, 8, 8, c)), jnp.zeros((3, 3, c, c)),
        jnp.ones((c,)), jnp.zeros((c,)), jnp.zeros((3, 3, c, c)),
        jnp.ones((c,)), jnp.zeros((c,)), jnp.zeros((1, 1, c, c)),
        jnp.ones((c,)), jnp.zeros((c,)),
    )
    with pytest.raises(ValueError, match="identity"):
        # an identity-geometry site must use fused_basic_block, not the
        # projection kernel (the shortcut conv would change the math)
        pallas_conv.fused_projection_block(
            *proj_args, stride=1, interpret=True
        )
    bot_args = (
        jnp.zeros((8, 8, 8, 32)), jnp.zeros((1, 1, 32, 8)),
        jnp.ones((8,)), jnp.zeros((8,)), jnp.zeros((3, 3, 8, 8)),
        jnp.ones((8,)), jnp.zeros((8,)), jnp.zeros((1, 1, 8, 32)),
        jnp.ones((32,)), jnp.zeros((32,)),
    )
    with pytest.raises(ValueError, match="shortcut"):
        # identity geometry (in == 4*planes, stride 1) with a shortcut
        # supplied: the static proj flag must match the geometry
        pallas_conv.fused_bottleneck_block(
            *bot_args,
            (jnp.zeros((1, 1, 32, 32)), jnp.ones((32,)), jnp.zeros((32,))),
            stride=1, interpret=True,
        )


# ---------------------------------------------------------------- module


def _models(**kw):
    mx = SupConResNet(model_name="resnet10", head="mlp", feat_dim=16, **kw)
    mp = SupConResNet(
        model_name="resnet10", head="mlp", feat_dim=16, conv_impl="pallas",
        **kw,
    )
    return mx, mp


@pytest.mark.parametrize("model_name", ["resnet10", "resnet50"])
def test_encoder_param_trees_impl_independent(model_name):
    """Init under both impls yields IDENTICAL trees (structure and values):
    the checkpoint contract that lets --conv_impl swap across restores —
    for the BasicBlock family AND the Bottleneck family (whose pallas
    branch shadows three convs + three BNs + the projection shortcut)."""
    kw = dict(model_name=model_name, head="mlp", feat_dim=16)
    mx = SupConResNet(**kw)
    mp = SupConResNet(conv_impl="pallas", **kw)
    vx = mx.init(jax.random.key(0), jnp.zeros((2, 8, 8, 3)), train=True)
    vp = mp.init(jax.random.key(0), jnp.zeros((2, 8, 8, 3)), train=True)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        vx, vp,
    )


def test_encoder_pallas_matches_xla_fwd_grads_stats(rng):
    mx, mp = _models()
    x = jnp.asarray(rng.standard_normal((8, 8, 8, 3)).astype(np.float32))
    v = mx.init(jax.random.key(0), jnp.zeros((2, 8, 8, 3)), train=True)

    def run(m):
        return m.apply(v, x, train=True, mutable=["batch_stats"])

    ox, mutx = run(mx)
    op, mutp = run(mp)
    np.testing.assert_allclose(
        np.asarray(ox), np.asarray(op), rtol=1e-4, atol=1e-4
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        ),
        mutx["batch_stats"], mutp["batch_stats"],
    )

    def loss(params, m):
        out, _ = m.apply(
            {"params": params, "batch_stats": v["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return jnp.sum(out * jnp.cos(out))

    gx = jax.grad(loss)(v["params"], mx)
    gp = jax.grad(loss)(v["params"], mp)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
        ),
        gx, gp,
    )


def test_encoder_eval_mode_stays_bitwise_xla(rng):
    """train=False never touches the fused path: bitwise-identical output
    (the validation/probe encode path keeps its pinned numerics)."""
    mx, mp = _models()
    x = jnp.asarray(rng.standard_normal((8, 8, 8, 3)).astype(np.float32))
    v = mx.init(jax.random.key(0), jnp.zeros((2, 8, 8, 3)), train=True)
    ex = mx.apply(v, x, train=False)
    ep = mp.apply(v, x, train=False)
    np.testing.assert_array_equal(np.asarray(ex), np.asarray(ep))


def test_unsupported_sites_fall_back_without_touching_kernels(
    rng, monkeypatch
):
    """Non-admitted compute dtypes (anything outside {fp32, bf16}) and
    eval mode must never call into ops/pallas_conv — proven by poisoning
    ALL FOUR fused entry points (stem, identity block, projection block,
    Bottleneck)."""

    def boom(*a, **k):
        raise AssertionError("fused kernel called on an unsupported path")

    for entry in ("fused_basic_block", "fused_projection_block",
                  "fused_bottleneck_block", "fused_conv_bn_relu"):
        monkeypatch.setattr(pallas_conv, entry, boom)
    x = jnp.asarray(rng.standard_normal((8, 8, 8, 3)).astype(np.float32))
    # fp16 is not an admitted compute dtype: every site falls back to XLA
    # (bf16 IS admitted since round 19 — covered by the bf16 parity tests)
    m_fp16 = SupConResNet(
        model_name="resnet10", head="mlp", feat_dim=16,
        conv_impl="pallas", dtype=jnp.float16,
    )
    v = m_fp16.init(jax.random.key(0), jnp.zeros((2, 8, 8, 3)), train=True)
    m_fp16.apply(v, x, train=True, mutable=["batch_stats"])  # xla fallback
    # same through a Bottleneck model (the new shadow modules)
    m50 = SupConResNet(
        model_name="resnet50", head="mlp", feat_dim=16,
        conv_impl="pallas", dtype=jnp.float16,
    )
    v50 = m50.init(jax.random.key(0), jnp.zeros((2, 8, 8, 3)), train=True)
    m50.apply(v50, x, train=True, mutable=["batch_stats"])  # xla fallback
    mx, mp = _models()
    v = mx.init(jax.random.key(0), jnp.zeros((2, 8, 8, 3)), train=True)
    mp.apply(v, x, train=False)  # eval: fused path must stay untouched


# ------------------------------------------------------------- resolution


def test_resolve_conv_impl_ladder(monkeypatch):
    from simclr_pytorch_distributed_tpu.train import supcon

    # explicit xla: honored anywhere
    impl, reason = supcon.resolve_conv_impl("xla", "resnet18", 256, 32, 1)
    assert impl == "xla" and "explicit" in reason
    # auto on CPU: xla, and the reason points at the open chip question
    impl, reason = supcon.resolve_conv_impl("auto", "resnet18", 256, 32, 1)
    assert impl == "xla" and "ROADMAP A1" in reason
    # auto on a TPU single chip: STILL xla — Mosaic refuses several of the
    # kernels the supports_* gates admit at this geometry
    # (tests/test_tpu_aot_compile.py), so no default run selects them
    monkeypatch.setattr(supcon.jax, "default_backend", lambda: "tpu")
    impl, reason = supcon.resolve_conv_impl("auto", "resnet18", 256, 32, 1)
    assert impl == "xla"
    assert "ROADMAP A1" in reason and "not yet shown" in reason
    # auto multi-device: xla as well
    impl, reason = supcon.resolve_conv_impl("auto", "resnet18", 256, 32, 8)
    assert impl == "xla"
    # auto + bf16: xla too (the bf16 stem's backward is refused)
    impl, reason = supcon.resolve_conv_impl(
        "auto", "resnet18", 256, 32, 1, bf16=True
    )
    assert impl == "xla"
    # explicit pallas on TPU names the compiled mode and the admitted sites
    impl, reason = supcon.resolve_conv_impl("pallas", "resnet18", 256, 32, 1)
    assert impl == "pallas" and "compiled" in reason
    assert "layer1_block0" in reason and "stem" in reason and "fp32" in reason
    impl, reason = supcon.resolve_conv_impl(
        "pallas", "resnet18", 256, 32, 1, bf16=True
    )
    assert "layer3_block0" in reason  # bf16-only site (half the VMEM)
    # explicit pallas + bf16: honored, the round-15 raise inverted
    impl, reason = supcon.resolve_conv_impl(
        "pallas", "resnet18", 256, 32, 1, bf16=True
    )
    assert impl == "pallas" and "bf16" in reason
    # rn50 resolves too (the Bottleneck kernel): no more stem-only edge
    impl, reason = supcon.resolve_conv_impl("pallas", "resnet50", 256, 32, 1)
    assert impl == "pallas" and "bottleneck" in reason
    # explicit pallas: still honored-or-raise on real contradictions
    with pytest.raises(ValueError, match="single-device"):
        supcon.resolve_conv_impl("pallas", "resnet18", 256, 32, 8)
    with pytest.raises(ValueError, match="admits no site"):
        # a geometry with zero admitted sites still raises, naming the
        # dtype it resolved under
        supcon.resolve_conv_impl("pallas", "resnet18", 2, 2, 1)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("model,n_devices,bf16", [
    ("resnet50", 1, False),  # run_supcon.sh on one chip
    ("resnet50", 1, True),
    ("resnet50", 4, False),  # ... and on the four-chip mesh
    ("resnet18", 1, True),
])
def test_resolve_conv_impl_auto_is_xla(monkeypatch, backend, model,
                                        n_devices, bf16):
    """``auto`` never selects a conv kernel on any backend, mesh or dtype
    until a chip cell shows one compiling and winning (ROADMAP A1)."""
    from simclr_pytorch_distributed_tpu.train import supcon

    monkeypatch.setattr(supcon.jax, "default_backend", lambda: backend)
    impl, reason = supcon.resolve_conv_impl(
        "auto", model, 256, 32, n_devices, bf16=bf16
    )
    assert impl == "xla" and "ROADMAP A1" in reason


def test_conv_fused_sites_geometry_walk():
    from simclr_pytorch_distributed_tpu.train import supcon

    sites = supcon.conv_fused_sites("resnet18", 512, 32)
    # stage 1 fully fused INCLUDING the stage-2 stride-2 projection lead
    # (admitted since round 19); VMEM-inadmissible late stages excluded
    assert "stem 3->64@32x32" in sites
    assert "layer1_block0[basic] 64->64@32x32/s1" in sites
    assert "layer2_block0[proj] 64->128@32x32/s2" in sites
    assert "layer2_block1[basic] 128->128@16x16/s1" in sites
    assert not any("layer3" in s or "layer4" in s for s in sites)
    # bf16 halves the per-site VMEM footprint: strictly more sites
    bf16_sites = supcon.conv_fused_sites(
        "resnet18", 512, 32, dtype=jnp.bfloat16
    )
    assert set(sites) < set(bf16_sites)
    assert "layer3_block0[proj] 128->256@16x16/s2" in bf16_sites
    # bottleneck models fuse real blocks now (round-15's stem-only edge
    # closed); the VMEM-rejected stride-2 stage-2 lead stays excluded
    r50 = supcon.conv_fused_sites("resnet50", 512, 32)
    assert "layer1_block0[bottleneck] 64->256@32x32/s1" in r50
    assert "layer2_block1[bottleneck] 512->512@16x16/s1" in r50
    assert not any("layer2_block0" in s for s in r50)
    # odd sizes: the walker halves like the stride-2 conv itself does
    # (ceil(h/2) under (1,1) padding), so the banner/raise geometry can
    # never diverge from the model's own per-site gates; odd-dim stride-2
    # sites themselves are NOT admitted (the kernels' even-dims rule)
    odd = supcon.conv_fused_sites("resnet18", 32, 33)
    assert "layer2_block1[basic] 128->128@17x17/s1" in odd
    assert not any("/s2" in s for s in odd)


def test_fused_site_plan_single_sources_the_walk():
    """The plan IS the geometry contract: every site row carries the block
    INPUT dims its admission was judged at, and re-consulting the
    supports_* gates with those dims reproduces the verdict — banner,
    module gate, and kernel wrapper can never disagree."""
    for model, dtype in (("resnet18", jnp.float32),
                         ("resnet50", jnp.bfloat16)):
        plan = fused_site_plan(model, 512, 32, dtype=dtype)
        assert plan[0]["kind"] == "stem"
        # one row per potential site: stem + every residual block
        from simclr_pytorch_distributed_tpu.models.resnet import MODEL_DICT

        n_blocks = sum(MODEL_DICT[model][0]().stage_sizes)
        assert len(plan) == 1 + n_blocks
        for site in plan[1:]:
            if site["kind"] == "bottleneck":
                regate = pallas_conv.supports_bottleneck(
                    512, site["h"], site["w"], site["width"],
                    stride=site["stride"], in_channels=site["in_channels"],
                    dtype=dtype,
                )
            else:
                regate = pallas_conv.supports_block(
                    512, site["h"], site["w"], site["width"],
                    stride=site["stride"], in_channels=site["in_channels"],
                    dtype=dtype,
                )
            assert site["admitted"] == regate, site["desc"]
            # identity vs projection dispatch keys on the same fields the
            # module branch reads
            if site["kind"] == "basic":
                assert site["stride"] == 1
                assert site["in_channels"] == site["width"]
            elif site["kind"] == "proj":
                assert site["stride"] != 1 or \
                    site["in_channels"] != site["width"]


def test_resolve_loss_impl_reasoned_names_degradations(monkeypatch):
    from simclr_pytorch_distributed_tpu.train import supcon

    impl, reason = supcon.resolve_loss_impl_reasoned("auto", 256, 1)
    assert impl == "dense" and "non-TPU" in reason
    impl, reason = supcon.resolve_loss_impl_reasoned("dense", 256, 1)
    assert impl == "dense" and reason == "explicit request"
    impl, reason = supcon.resolve_loss_impl_reasoned(
        "auto", 256, 1, moco_queue=512
    )
    assert impl == "dense" and "moco_queue" in reason
    monkeypatch.setattr(supcon.jax, "default_backend", lambda: "tpu")
    impl, reason = supcon.resolve_loss_impl_reasoned("auto", 256, 1)
    assert impl == "fused" and "single-chip" in reason
    impl, reason = supcon.resolve_loss_impl_reasoned("auto", 3, 1)
    assert impl == "dense" and "tile" in reason


def test_impl_resolution_banner_format():
    line = config_lib.impl_resolution_banner(
        "conv_impl", "auto", "xla", "non-TPU backend (cpu)"
    )
    assert line == (
        "[conv_impl] requested 'auto' -> resolved 'xla': non-TPU backend (cpu)"
    )
    same = config_lib.impl_resolution_banner(
        "conv_impl", "xla", "xla", "explicit request"
    )
    assert same == "[conv_impl] 'xla': explicit request"


def test_build_logs_resolution_banners(tmp_path, caplog):
    import logging

    from simclr_pytorch_distributed_tpu.train.supcon import build

    cfg = config_lib.SupConConfig(
        model="resnet10", dataset="synthetic", batch_size=8, epochs=1,
        size=8, workdir=str(tmp_path),
    )
    cfg = config_lib.finalize_supcon(cfg, make_dirs=False)
    with caplog.at_level(logging.INFO):
        build(cfg, steps_per_epoch=4, n_devices=1)
    text = caplog.text
    assert "[conv_impl]" in text and "[loss_impl]" in text


def test_validate_conv_impl_admits_pallas_bf16():
    """The round-15 parse-time pallas+bf16 rejection is GONE: admission is
    per-site at resolution time (resolve_conv_impl), where the actual
    geometry and backend are known. The seam stays callable and silent."""
    config_lib.validate_conv_impl(
        config_lib.SupConConfig(conv_impl="pallas", bf16=True)
    )
    config_lib.validate_conv_impl(
        config_lib.SupConConfig(conv_impl="auto", bf16=True)
    )


def test_parser_accepts_conv_impl():
    p = config_lib.supcon_parser()
    ns = p.parse_args(["--conv_impl", "pallas"])
    assert ns.conv_impl == "pallas"
    assert p.parse_args([]).conv_impl == "auto"


def test_pallas_bf16_parses_and_finalizes(tmp_path):
    """--conv_impl pallas --bf16 survives the full parse->finalize
    pipeline (the round-15 parse-time rejection, inverted): admission is
    resolution-time now."""
    cfg = config_lib.SupConConfig(
        model="resnet18", dataset="synthetic", conv_impl="pallas",
        bf16=True, workdir=str(tmp_path),
    )
    out = config_lib.finalize_supcon(cfg, make_dirs=False)
    assert out.conv_impl == "pallas" and out.bf16


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


# ----------------------------------------------------- real-driver smoke


@pytest.mark.parametrize("model,bf16", [("resnet10", False),
                                        ("resnet50", True)])
def test_driver_pallas_checkpoint_restores_under_xla(
    tmp_path, monkeypatch, model, bf16
):
    """2-epoch --conv_impl pallas pretrain through the REAL driver, then a
    resume under --conv_impl xla: the param tree is impl-independent, so
    the restore continues the trajectory (and the banners name both
    resolutions). Run once for the BasicBlock family in fp32 and once for
    rn50's Bottleneck family on the bf16 arm — the two new round-19
    fused-ladder ends."""
    from simclr_pytorch_distributed_tpu.data import cifar as cifar_lib
    from simclr_pytorch_distributed_tpu.parallel import mesh as mesh_lib
    from simclr_pytorch_distributed_tpu.train import supcon as supcon_driver

    orig = cifar_lib.synthetic_dataset

    def small(n=2048, num_classes=10, seed=0, size=32):
        return orig(n=104, num_classes=num_classes, seed=seed, size=8)

    monkeypatch.setattr(cifar_lib, "synthetic_dataset", small)

    def limited_create_mesh(devices=None, **kw):
        if devices is None:
            devices = jax.devices()[:1]
        return mesh_lib.create_mesh(devices=devices, **kw)

    monkeypatch.setattr(supcon_driver, "create_mesh", limited_create_mesh)

    def cfg_for(conv_impl, epochs, resume=""):
        cfg = config_lib.SupConConfig(
            model=model, dataset="synthetic", batch_size=32, epochs=epochs,
            learning_rate=0.05, temp=0.5, size=8, workdir=str(tmp_path),
            save_freq=1, print_freq=2, seed=0, method="SimCLR",
            conv_impl=conv_impl, resume=resume, health_freq=0, bf16=bf16,
        )
        return config_lib.finalize_supcon(cfg)

    cfg1 = cfg_for("pallas", epochs=2)
    state1 = supcon_driver.run(cfg1)
    steps1 = int(state1.step)
    assert steps1 > 0
    # restore the pallas-written checkpoint under the xla impl
    cfg2 = cfg_for("xla", epochs=3, resume=f"{cfg1.save_folder}/last")
    state2 = supcon_driver.run(cfg2)
    assert int(state2.step) == steps1 // 2 * 3
