"""The causal depthwise convolution's kernel pair (ops/short_conv.py) against
XLA's path, ``jax.nn.silu(models/gated_delta.short_conv(...))``, and its
``jax.vjp``.

CPU, Pallas interpret mode: correctness only. The kernels add the same
float32 products in the same order as XLA's path, so they are held to
float32's noise; the tap gradients are sums over a row in another order.
"""

import jax
import jax.numpy as jnp
import pytest

from simclr_pytorch_distributed_tpu.models import gated_delta, token_encoder
from simclr_pytorch_distributed_tpu.ops import short_conv as sc

R, T, C = 2, 128, 256


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30))


def _inputs(taps, width, seed=0):
    """``x [R, T, width]``, ``w [taps, C]`` and a cotangent ``dy [R, T, C]``;
    ``x`` grows along the row, so that a token moved by one place reads
    visibly another value."""
    kx, kw, kd = jax.random.split(jax.random.key(seed), 3)
    ramp = jnp.linspace(-2.0, 2.0, T)[None, :, None]
    x = jax.random.normal(kx, (R, T, width)) + ramp
    w = jax.random.uniform(kw, (taps, C), minval=-1.0, maxval=1.0)
    return x, w, jax.random.normal(kd, (R, T, C))


def _xla(x, w):
    return jax.nn.silu(gated_delta.short_conv(x[..., :w.shape[1]], w))


def _kernel(x, w):
    return sc.short_conv_silu(x, w, interpret=True)


@pytest.mark.parametrize("taps", [2, 4])
@pytest.mark.parametrize("width", [C, 3 * C // 2], ids=["x-alone", "x-in-a-wider-array"])
@pytest.mark.parametrize("block", [32, 64], ids=["4-blocks-a-row", "2-blocks-of-2-slabs"])
def test_forward_and_gradients_are_xlas(monkeypatch, taps, width, block):
    """Output, ``dx`` and ``dw`` at several token blocks a row, so that the
    carried tokens cross block edges both ways (the forward's ``x`` onward,
    the backward's ``du`` back), and ``x`` read from the first ``C`` columns
    of a wider array, whose other columns get a zero gradient."""
    monkeypatch.setattr(sc, "TOKEN_BLOCK", block)
    assert sc.unsupported(T, C, taps, jnp.float32) is None
    x, w, dy = _inputs(taps, width)
    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(_xla, x, w)
        got, got_vjp = jax.vjp(_kernel, x, w)
        (want_dx, want_dw), (got_dx, got_dw) = want_vjp(dy), got_vjp(dy)
    assert got.shape == (R, T, C) and got_dx.shape == x.shape and got_dw.shape == w.shape
    assert _rel(got, want) < 1e-6
    assert _rel(got_dx, want_dx) < 1e-6 and bool(jnp.all(got_dx[..., C:] == 0))
    assert _rel(got_dw, want_dw) < 1e-5
    # the taps reach back across every block edge: a convolution that lost
    # the carried tokens differs there by far more than the tolerance
    unlinked = jax.nn.silu(jnp.concatenate(
        [gated_delta.short_conv(x[:, t:t + block, :C], w) for t in range(0, T, block)], axis=1))
    assert _rel(unlinked, want) > 1e-3


def test_the_first_tokens_see_zeros_before_them(monkeypatch):
    """Token 0 sums only the tap on itself: each row starts afresh, whatever
    the row before it ended with."""
    monkeypatch.setattr(sc, "TOKEN_BLOCK", 64)
    taps = 4
    x, w, _ = _inputs(taps, C)
    got = _kernel(x, w)
    first = jax.nn.silu(x[:, 0, :C] * w[taps - 1])
    assert _rel(got[:, 0], first) < 1e-6


# ------------------------------------------------------------- the predicate


@pytest.mark.parametrize("args,why", [
    ((4096, 8192, 4, jnp.float32), None),
    ((4096, 8192, 4, jnp.bfloat16), "compute dtype bfloat16"),
    ((16, 64, 4, jnp.float32), "64 channels are not a multiple of 128 lanes"),
    ((4000, 8192, 4, jnp.float32), "4000 tokens a row do not cut into blocks of 512"),
    ((4096, 8192, 10, jnp.float32), "10 taps reach past the 8 tokens a block is given"),
], ids=["the-cell", "bf16", "tiny-channels", "tokens-off-the-block", "too-many-taps"])
def test_unsupported_says_why(args, why):
    reason = sc.unsupported(*args)
    assert reason is None if why is None else why == reason, reason


def test_unsupported_refuses_what_is_over_budget(monkeypatch):
    """Blocks of 4,096 tokens: six double-buffered 8 MiB blocks a step."""
    monkeypatch.setattr(sc, "TOKEN_BLOCK", 4096)
    assert "48.1 MiB of VMEM a step (budget 14)" in sc.unsupported(4096, 8192, 4, jnp.float32)
    assert sc.channel_block(8192) == 512 and sc.channel_block(384) == 128


# ------------------------------------------------------------------ the layer


def _layer(**kw):
    return gated_delta.GatedDeltaNet(**{
        "n_key_heads": 2, "n_value_heads": 4, "key_dim": 128, "value_dim": 128,
        "conv_width": 4, "chunk": 8, "rms_eps": 1e-6, "kernel": True, **kw})


def _run(layer, h, params):
    def loss(params, h):
        out, decay = layer.apply({"params": params}, h)
        return jnp.sum(jnp.sin(out)), (out, decay)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(params, h)


def _pallas_calls(layer, h):
    params = jax.eval_shape(lambda: layer.init(jax.random.key(0), h))

    def loss(params, h):
        return jnp.sum(layer.apply(params, h)[0])

    text = str(jax.make_jaxpr(jax.grad(loss))(params, h))
    return text.count("name=short_conv_"), text.count("name=delta_rule_")


@pytest.mark.parametrize("widths", [128, 64], ids=["both-pairs", "the-conv-alone"])
def test_the_layer_with_the_conv_kernel_is_the_layer_without(monkeypatch, widths):
    """Output, mean decay and the gradients of every weight and of the
    input, over two row groups of 64 tokens in two blocks each, with the
    convolution's kernel pair on and off (blocks of 48 tokens do not cut
    the row: ``conv_reason`` says so and the layer takes XLA's). With heads
    of 64 the rule stays on XLA's path and the convolution engages alone:
    each pair answers for itself."""
    h = jax.random.normal(jax.random.key(4), (4, 64, 64))
    layer = _layer(key_dim=widths, value_dim=widths)
    params = layer.init(jax.random.key(5), h)["params"]
    params = {name: 3 * w if w.ndim == 2 else w for name, w in params.items()}
    monkeypatch.setattr(sc, "TOKEN_BLOCK", 32)
    assert layer.conv_reason(64) is None
    assert (layer.kernel_reason(64) is None) is (widths == 128)
    rule_calls = 3 if widths == 128 else 0
    assert _pallas_calls(layer, h) == (3, rule_calls)
    with jax.default_matmul_precision("highest"):
        (_, (got, got_decay)), got_grads = _run(layer, h, params)
        monkeypatch.setattr(sc, "TOKEN_BLOCK", 48)
        assert layer.conv_reason(64) == "64 tokens a row do not cut into blocks of 48"
        assert _pallas_calls(layer, h) == (0, rule_calls)
        (_, (want, want_decay)), want_grads = _run(layer, h, params)
    assert _rel(got - h, want - h) < 1e-6
    assert float(got_decay) == pytest.approx(float(want_decay), rel=1e-6)
    flat = jax.tree_util.tree_leaves_with_path(got_grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.linalg.norm(w)) > 0, jax.tree_util.keystr(path)
        assert _rel(g, w) < 1e-5, (jax.tree_util.keystr(path), _rel(g, w))


@pytest.mark.parametrize("attrs,tokens,why", [
    ({}, 4096, None),
    ({"dtype": jnp.bfloat16}, 4096, "compute dtype bfloat16"),
    ({"key_dim": 8, "value_dim": 8, "n_key_heads": 2, "n_value_heads": 4}, 16,
     "64 channels are not a multiple of 128 lanes"),
    ({}, 1024 + 256, "1280 tokens a row do not cut into blocks of 512"),
], ids=["the-cell", "bf16", "tiny-widths", "row-off-the-block"])
def test_conv_reason_says_why(attrs, tokens, why):
    """The real preset's layer at float32 takes the convolution's kernel
    pair at the cell's 4,096 tokens (8,192 channels); each of the others
    says why it does not."""
    spec = token_encoder.TOKEN_ENCODERS["qwen3-next-80b-a3b-ep32"]
    layer = gated_delta.GatedDeltaNet(**{**token_encoder.delta_attrs(spec, jnp.float32, True),
                                        **attrs})
    assert layer.conv_reason(tokens) == why
