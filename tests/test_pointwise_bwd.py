"""Bottleneck's tail through one backward kernel (ops/pointwise_bwd.py).

CPU, Pallas interpret mode: correctness only. The routed path is XLA's
forward with a custom backward, so the forward must be the unrouted one bit
for bit, and the backward must be the plain one with its two products taken
at the TPU's default precision (bfloat16 operands, float32 accumulation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax

from simclr_pytorch_distributed_tpu import config as config_lib
from simclr_pytorch_distributed_tpu.models import resnet as resnet_lib
from simclr_pytorch_distributed_tpu.models.resnet import Bottleneck, ResNet
from simclr_pytorch_distributed_tpu.ops import pointwise_bwd as pb
from simclr_pytorch_distributed_tpu.train import supcon
from simclr_pytorch_distributed_tpu.train.state import create_train_state
from simclr_pytorch_distributed_tpu.utils import checkpoint, tracing

EPS = 1e-5
NAMES = ("dz", "dmean2", "dinv2", "dscale2", "dbias2", "dw", "dscale3", "dbias3")


def _tail_inputs(rows, hw, c, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    wide = 4 * c
    z = jax.random.normal(ks[0], (rows, hw, hw, c)) * 1.3 + 0.2
    mean2, var2 = pb.batch_moments(z)
    inv2 = lax.rsqrt(var2 + EPS)
    args = (
        z, mean2, inv2,
        1 + 0.1 * jax.random.normal(ks[1], (c,)),
        0.1 * jax.random.normal(ks[2], (c,)),
        jax.random.normal(ks[3], (1, 1, c, wide)) * (2 / wide) ** 0.5,
        1 + 0.1 * jax.random.normal(ks[4], (wide,)),
        0.1 * jax.random.normal(ks[5], (wide,)),
    )
    return args, jax.random.normal(ks[6], (rows, hw, hw, wide))


def _plain_backward(z, mean2, inv2, scale2, bias2, w, scale3, bias3, dy):
    """The tail's backward in plain jnp: batch norm's backward as the
    per-channel constants the module shares (they are jnp there too), then
    the two products with bfloat16-rounded operands, float32 accumulation."""

    def rounded(t):
        return t.astype(jnp.bfloat16).astype(jnp.float32)

    hp = lax.Precision.HIGHEST
    c, wide = w.shape[2:]
    w2 = w.reshape(c, wide)
    zc = z - mean2
    u = zc * inv2 * scale2 + bias2
    a = jnp.maximum(u, 0.0)
    x3, inv3 = pb._forward(z, mean2, inv2, scale2, bias2, w, scale3, bias3, EPS)[3:]
    mean3 = x3.mean((0, 1, 2))
    dbias3, dscale3, gv = pb._bn3_backward(dy, x3, mean3, inv3, scale3)
    g = gv[0] * dy + gv[1] * x3 + gv[2]
    da = jnp.einsum("nhwd,cd->nhwc", rounded(g), rounded(w2), precision=hp)
    dw = jnp.einsum("nhwc,nhwd->cd", rounded(a), rounded(g), precision=hp)
    du = jnp.where(u > 0, da, 0.0)
    s0, s1 = du.sum((0, 1, 2)), (du * zc).sum((0, 1, 2))
    return (du * (inv2 * scale2), -inv2 * scale2 * s0, scale2 * s1, inv2 * s1,
            s0, dw.reshape(w.shape), dscale3, dbias3)


def _routed_vjp(args, dy):
    wide = dy.shape[-1]
    _, vjp = jax.vjp(
        lambda *a: pb.expand_conv_bn(*a, eps=EPS, interpret=True), *args
    )
    return vjp((dy, jnp.zeros(wide), jnp.zeros(wide)))


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("c,rows", [(64, 256), (128, 256)],
                         ids=["batch-minor-C64", "channel-minor-C128"])
def test_backward_matches_plain_bf16_products(c, rows):
    """Both operand orders, over four grid steps (a spatial position each),
    so ``dw`` and the two sums are accumulated across blocks."""
    args, dy = _tail_inputs(rows, 2, c)
    got = _routed_vjp(args, dy)
    want = _plain_backward(*args, dy)
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape, name
        assert _rel(g, w) < 1e-5, (name, _rel(g, w))


def test_backward_is_the_forwards_gradient():
    """Against autodiff of the same forward, whose products are exact on the
    CPU: the kernel's bfloat16 operands are the whole difference, and bn3's
    parameter gradients, which no product enters, agree to rounding."""
    args, dy = _tail_inputs(128, 2, 64, seed=1)
    got = _routed_vjp(args, dy)
    _, vjp = jax.vjp(lambda *a: pb._forward(*a, EPS)[0], *args)
    want = vjp(dy)
    for name, g, w in zip(NAMES, got, want):
        assert _rel(g, w) < (1e-5 if name in ("dscale3", "dbias3") else 1e-2), name


def test_unsupported_names_what_does_not_tile():
    assert "batch-minor" in pb.unsupported(64, 64, 256)  # rows under a lane width
    assert pb.unsupported(24, 128, 512) is not None
    assert "multiple of 128" in pb.unsupported(512, 16, 64)


@pytest.mark.parametrize("rows,c,mosaic_mib", [
    (512, 64, None), (512, 128, None), (512, 256, None), (1024, 128, None),
    (512, 512, 26.04), (384, 512, 21.04), (256, 512, 16.04),
    (768, 256, 16.52), (1024, 256, 21.52), (2048, 128, 20.38),
    (4096, 64, 20.10),
], ids=lambda v: str(v))
def test_unsupported_keeps_a_block_inside_the_vmem_budget(rows, c, mosaic_mib):
    """A block is all rows of one position. ``mosaic_mib`` is what the v5e's
    compiler said it needs where it refused the shape against its 16 MiB
    (asked without the chip, PR 26): ``vmem_bytes`` is that count less the
    per-channel vectors (under 50 KiB), and every such shape, 16.04 included,
    stays on XLA's path."""
    reason = pb.unsupported(rows, c, 4 * c)
    if mosaic_mib is None:
        assert reason is None
    else:
        assert "MiB of VMEM" in reason
        assert 0 <= mosaic_mib - pb.vmem_bytes(rows, c, 4 * c) / 2**20 < 0.05


def _small_encoder(**kw):
    return ResNet(block_cls=Bottleneck, stage_sizes=(1, 1, 1, 1), **kw)


def _pallas_calls(fn, *args) -> int:
    """Calls of this kernel."""
    return str(jax.make_jaxpr(fn)(*args)).count("name=pointwise_bwd")


@pytest.fixture(scope="module")
def small_setup():
    x = jax.random.normal(jax.random.key(3), (128, 8, 8, 3))
    variables = _small_encoder().init(jax.random.key(0), x[:2], train=True)
    return x, variables


def test_routed_forward_is_bitwise_and_trees_agree(small_setup):
    x, variables = small_setup
    outs = {}
    for routed in (False, True):
        model = _small_encoder(pointwise_bwd=routed)
        outs[routed] = jax.jit(
            lambda v, x, m=model: m.apply(v, x, train=True, mutable=["batch_stats"])
        )(variables, x)
        inited = model.init(jax.random.key(0), x[:2], train=True)
        assert (jax.tree_util.tree_structure(inited)
                == jax.tree_util.tree_structure(variables))
    (y0, stats0), (y1, stats1) = outs[False], outs[True]
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert (jax.tree_util.tree_structure(stats0)
            == jax.tree_util.tree_structure(stats1))
    for a, b in zip(jax.tree_util.tree_leaves(stats0),
                    jax.tree_util.tree_leaves(stats1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_routed_gradient_is_the_unrouted_one_to_bf16(small_setup):
    """The whole encoder: every leaf's gradient against XLA's path. On the
    CPU that path's products are exact, so the distance is the kernel's
    bfloat16 operands (on the TPU both round alike)."""
    x, variables = small_setup

    def loss(params, model):
        y, _ = model.apply({**variables, "params": params}, x, train=True,
                           mutable=["batch_stats"])
        return jnp.mean(jnp.square(y))

    grads = {
        routed: jax.jit(jax.grad(
            lambda p, m=_small_encoder(pointwise_bwd=routed): loss(p, m)
        ))(variables["params"])
        for routed in (False, True)
    }
    flat0 = jax.tree_util.tree_leaves_with_path(grads[False])
    flat1 = jax.tree_util.tree_leaves(grads[True])
    for (path, g0), g1 in zip(flat0, flat1):
        assert _rel(g1, g0) < 3e-2, (jax.tree_util.keystr(path), _rel(g1, g0))


@pytest.mark.parametrize("case,encoder_kw,apply_kw,calls", [
    ("routed", {}, {}, 4),
    ("eval", {}, {"train": False}, 0),
    ("bf16", {"dtype": jnp.bfloat16}, {}, 0),
    ("grouped-bn", {"sync_bn": False, "bn_local_groups": 2}, {}, 0),
    ("axis-name", {"axis_name": "data"}, {"train": False}, 0),
    ("owner-says-no", {"pointwise_bwd": False}, {}, 0),
], ids=lambda v: v if isinstance(v, str) else None)
def test_fallbacks_take_xlas_path(small_setup, case, encoder_kw, apply_kw, calls):
    """One kernel per Bottleneck where everything holds; none otherwise."""
    x, variables = small_setup
    model = _small_encoder(**{"pointwise_bwd": True, **encoder_kw})
    train = apply_kw.get("train", True)

    def loss(params):
        out = model.apply({**variables, "params": params}, x, train=train,
                          mutable=["batch_stats"] if train else False)
        return jnp.mean(jnp.square((out[0] if train else out).astype(jnp.float32)))

    assert _pallas_calls(jax.grad(loss), variables["params"]) == calls


def test_axis_name_keeps_train_mode_on_xla(small_setup):
    x, variables = small_setup
    model = _small_encoder(pointwise_bwd=True, axis_name="data")

    def loss(params, xs):
        y, _ = model.apply({**variables, "params": params}, xs, train=True,
                           mutable=["batch_stats"])
        return jnp.mean(jnp.square(y))

    fn = jax.vmap(jax.grad(loss), in_axes=(None, 0), axis_name="data")
    assert _pallas_calls(fn, variables["params"], x.reshape(1, *x.shape)) == 0


def test_init_takes_xlas_path():
    x = jnp.zeros((128, 8, 8, 3))
    model = _small_encoder(pointwise_bwd=True)

    def loss(x):
        variables = model.init(jax.random.key(0), x, train=True)
        return sum(jnp.sum(v) for v in jax.tree_util.tree_leaves(variables))

    assert _pallas_calls(jax.grad(loss), x) == 0


def _cfg(**kw):
    return config_lib.SupConConfig(**{
        "model": "resnet50", "dataset": "synthetic", "batch_size": 64, "size": 8,
        "epochs": 1, "learning_rate": 0.5, "method": "SimCLR", "syncBN": True,
        **kw,
    })


def _build_and_count(cfg, n_devices):
    """``(plan event, pallas calls in the train-mode gradient)`` of
    ``train.supcon.build``'s model, nothing run."""
    rec = tracing.FlightRecorder(clock=lambda: 0.0)
    tracing.install(rec)
    built = {}
    try:
        def abstract():
            model, _, _, state, _ = supcon.build(cfg, 10, n_devices)
            built["model"] = model
            return state

        state = jax.eval_shape(abstract)
    finally:
        tracing.uninstall()
    events = [r for r in rec.snapshot() if r["name"] == "pointwise_bwd_plan"]
    assert len(events) == 1 and events[0]["track"] == "compile"
    model = built["model"]
    x = jax.ShapeDtypeStruct((2 * cfg.batch_size, cfg.size, cfg.size, 3), jnp.float32)

    def loss(params, batch_stats, x):
        y, _ = model.apply({"params": params, "batch_stats": batch_stats}, x,
                           train=True, mutable=["batch_stats"])
        return jnp.mean(jnp.square(y.astype(jnp.float32)))

    calls = _pallas_calls(jax.grad(loss), state.params, state.batch_stats, x)
    return events[0]["args"], calls


@pytest.mark.parametrize("case,cfg_kw,n_devices,backend,engaged,why", [
    ("one-tpu", {}, 1, "tpu", 16, None),
    ("stage-4-over-vmem", {"batch_size": 256}, 1, "tpu", 13, "MiB of VMEM"),
    ("stages-3-4-over-vmem", {"batch_size": 384}, 1, "tpu", 7, "MiB of VMEM"),
    ("bf16", {"bf16": True}, 1, "tpu", 0, "bfloat16"),
    ("grouped-bn", {"syncBN": False}, 4, "tpu", 0, "4 devices"),
    ("four-devices", {}, 4, "tpu", 0, "4 devices"),
    ("cpu", {}, 1, "cpu", 0, "non-TPU"),
    ("rows-do-not-tile", {"batch_size": 32}, 1, "tpu", 13, "batch-minor"),
    ("rn18", {"model": "resnet18"}, 1, "tpu", 0, None),
], ids=lambda v: v if isinstance(v, str) else None)
def test_build_plans_and_routes(monkeypatch, case, cfg_kw, n_devices, backend,
                                engaged, why):
    """``build`` decides from what it can see, says it once (banner + one
    ``pointwise_bwd_plan`` event), and the model's gradient holds exactly
    the planned number of kernels: none under --bf16, per-device BN groups,
    more than one device or off the TPU."""
    monkeypatch.setattr(supcon.jax, "default_backend", lambda: backend)
    plan, calls = _build_and_count(_cfg(**cfg_kw), n_devices)
    n_sites = 0 if cfg_kw.get("model") == "resnet18" else 16
    assert plan["engaged"] == engaged and plan["on_xla"] == n_sites - engaged
    assert calls == engaged
    if why is None:
        assert plan["reasons"] == {}
    else:
        assert all(why in reason for reason in plan["reasons"]), plan["reasons"]
        assert sum(len(v) for v in plan["reasons"].values()) == n_sites - engaged


@pytest.mark.parametrize("encoder_kw,why", [
    ({}, None),
    ({"sync_bn": False}, None),
    ({"sync_bn": False, "bn_local_groups": 2}, "groups"),
    ({"axis_name": "data"}, "axis"),
    ({"dtype": jnp.bfloat16}, "bfloat16"),
], ids=lambda v: str(v))
def test_tail_bwd_reason_is_the_plans_and_the_modules(encoder_kw, why):
    """One predicate: what the plan says of a site is what the encoder's
    ``__call__`` does there."""
    reason = _small_encoder(**encoder_kw).tail_bwd_reason(128, 64)
    assert reason is None if why is None else why in reason
    plan = resnet_lib.tail_bwd_plan("resnet50", 128, **encoder_kw)
    assert [site["reason"] for site in plan] == [reason] * 16
    assert resnet_lib.tail_bwd_plan("resnet50", 128, "owner", **encoder_kw)[0] == {
        "name": "layer1_block0", "reason": "owner"}


@pytest.mark.parametrize("written_routed", [True, False],
                         ids=["routed-to-xla", "xla-to-routed"])
def test_checkpoint_restores_on_the_other_path(tmp_path, written_routed):
    x = jax.random.normal(jax.random.key(5), (128, 8, 8, 3))
    tx = optax.sgd(0.1)

    def state_of(routed):
        model = _small_encoder(pointwise_bwd=routed)
        return model, create_train_state(model, tx, jax.random.key(1), x[:2])

    writer, state = state_of(written_routed)
    _, new_stats = writer.apply(
        {"params": state.params, "batch_stats": state.batch_stats}, x,
        train=True, mutable=["batch_stats"],
    )
    state = state.replace(batch_stats=new_stats["batch_stats"])
    checkpoint.save_checkpoint(str(tmp_path), "last", state, epoch=1)
    reader, blank = state_of(not written_routed)
    restored, _ = checkpoint.restore_checkpoint(str(tmp_path / "last"), blank)
    assert (jax.tree_util.tree_structure(restored.params)
            == jax.tree_util.tree_structure(state.params))
    for a, b in zip(
        jax.tree_util.tree_leaves((restored.params, restored.batch_stats)),
        jax.tree_util.tree_leaves((state.params, state.batch_stats)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the restored state runs on the reader's path
    y, _ = reader.apply(
        {"params": restored.params, "batch_stats": restored.batch_stats}, x,
        train=True, mutable=["batch_stats"],
    )
    assert bool(jnp.all(jnp.isfinite(y)))
