"""The chunked gated delta rule's kernel pair (ops/delta_rule.py) against
XLA's path (models/gated_delta.chunked_delta_rule) and the recurrence token
by token (benchmark/reference_delta.delta_rule).

CPU, Pallas interpret mode: correctness only. With float32 ``operands`` the
kernels multiply what XLA's CPU path multiplies, exactly, so they are held
to float32's noise; with the TPU's bfloat16 operands they are held to XLA's
path with every product's operands rounded to bfloat16, which is what the
TPU's default precision does to the same products.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_delta  # noqa: E402

from simclr_pytorch_distributed_tpu import config as config_lib  # noqa: E402
from simclr_pytorch_distributed_tpu.models import gated_delta, token_encoder  # noqa: E402
from simclr_pytorch_distributed_tpu.ops import delta_rule as dr  # noqa: E402
from simclr_pytorch_distributed_tpu.train import supcon  # noqa: E402
from simclr_pytorch_distributed_tpu.utils import tracing  # noqa: E402

R, T, HK, HV, D, CHUNK = 2, 64, 2, 4, 128, 16
NAMES = ("dq", "dk", "dv", "dg", "dbeta")
# log decay a token at most, beta's bias, the keys' noise around a shared direction
REGIMES = {"slow-decay": (0.2, 0.0, 0.3), "decay-20": (20.0, 0.0, 0.3),
           "beta-near-1": (2.0, 8.0, 0.3), "keys-alike": (0.2, 0.0, 0.05)}


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30))


def _inputs(regime, seed=0):
    """``q``, ``k`` ``[R, T, HK, D]`` L2-normed (``q`` scaled), ``v [R, T, HV,
    D]`` normal, ``g`` uniform in ``(-decay, 0)``, ``beta`` sigmoid of its
    bias plus noise; a row's keys a shared direction plus ``noise``, as a flat
    image tile's are; and a cotangent for ``o``."""
    decay, beta_bias, noise = REGIMES[regime]
    ks = jax.random.split(jax.random.key(seed), 7)
    base = jax.random.normal(ks[0], (R, 1, HK, D))
    k = gated_delta.l2_normalise(base + noise * jax.random.normal(ks[1], (R, T, HK, D)))
    q = gated_delta.l2_normalise(jax.random.normal(ks[2], (R, T, HK, D))) / np.sqrt(D)
    v = jax.random.normal(ks[3], (R, T, HV, D))
    g = -decay * jax.random.uniform(ks[4], (R, T, HV))
    beta = jax.nn.sigmoid(beta_bias + jax.random.normal(ks[5], (R, T, HV)))
    return (q, k, v, g, beta), jax.random.normal(ks[6], (R, T, HV * D))


def _kernel(q, k, v, g, beta, operands=jnp.float32):
    """The kernel pair in the projections' layout: ``o [R, T, HV*D]``."""
    return dr.delta_rule(q.reshape(R, T, -1), k.reshape(R, T, -1), v.reshape(R, T, -1), g, beta,
                         n_key_heads=HK, chunk=CHUNK, operands=operands, interpret=True)


def _repeated(q, k):
    """``q``, ``k`` copied to the value heads, as XLA's path takes them."""
    return (jnp.repeat(x, HV // HK, axis=2) for x in (q, k))


def _xla(q, k, v, g, beta):
    """XLA's path over ``q``, ``k`` already on the value heads."""
    return gated_delta.chunked_delta_rule(q, k, v, g, beta, CHUNK).reshape(R, T, -1)


@pytest.fixture(params=[1, 4], ids=["1-key-head-a-step", "4-key-heads-a-step"])
def key_heads(request, monkeypatch):
    """A grid step over one key head (two blocks of heads) or all of them."""
    monkeypatch.setattr(dr, "KEY_HEADS", request.param)
    return request.param


@pytest.mark.parametrize("regime", REGIMES)
def test_forward_is_the_chunked_rule_and_the_recurrence(regime, key_heads):
    """Outputs against XLA's path and against the recurrence token by token:
    strong decay (``g`` to -20 a token: ``exp(-G)`` alone would overflow
    within a chunk), ``beta`` near 1 and keys alike included."""
    (q, k, v, g, beta), _ = _inputs(regime)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_kernel)(q, k, v, g, beta)
        xla = jax.jit(_xla)(*_repeated(q, k), v, g, beta)
        token_by_token = jax.jit(jax.vmap(reference_delta.delta_rule))(
            *_repeated(q, k), v, g, beta).reshape(R, T, -1)
    assert bool(jnp.all(jnp.isfinite(got))) and float(jnp.linalg.norm(xla)) > 0.1
    assert _rel(got, xla) < 1e-5
    assert _rel(got, token_by_token) < 2e-5


@pytest.mark.parametrize("regime", REGIMES)
def test_gradients_are_autodiff_of_the_chunked_rule(regime, key_heads):
    """The custom VJP's gradients of all five inputs against autodiff of XLA's
    path. ``dq`` and ``dk`` of a key head are the sums of XLA's gradients of
    its two value heads' copies, taken here explicitly."""
    (q, k, v, g, beta), ct = _inputs(regime)
    q_rep, k_rep = _repeated(q, k)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda *a: jnp.sum(ct * _kernel(*a)), argnums=range(5)))(
            q, k, v, g, beta)
        want = jax.jit(jax.grad(lambda *a: jnp.sum(ct * _xla(*a)), argnums=range(5)))(
            q_rep, k_rep, v, g, beta)
    per_key = HV // HK
    summed = [x.reshape(R, T, HK, per_key, D).sum(axis=3) for x in want[:2]]
    assert _rel(summed[0], want[0][:, :, ::per_key]) > 0.1  # the copies' gradients differ
    for name, g_got, g_want in zip(NAMES, got, (*summed, *want[2:])):
        assert bool(jnp.all(jnp.isfinite(g_got))), name
        assert float(jnp.linalg.norm(g_want)) > 0, name
        assert _rel(g_got, g_want) < 1e-5, (name, _rel(g_got, g_want))


def _eval_rounding(jaxpr, consts, *args):
    """``jaxpr`` evaluated with every ``dot_general`` reading bfloat16
    operands and accumulating float32; loops and calls evaluated alike."""
    env = {}

    def read(var):
        return var.val if type(var).__name__ == "Literal" else env[var]

    env.update(zip(jaxpr.constvars, consts))
    env.update(zip(jaxpr.invars, args))
    for eqn in jaxpr.eqns:
        x, params = [read(var) for var in eqn.invars], eqn.params
        if eqn.primitive.name == "dot_general":
            out = [lax.dot_general(*(a.astype(jnp.bfloat16) for a in x),
                                   params["dimension_numbers"],
                                   preferred_element_type=jnp.float32)]
        elif eqn.primitive.name == "scan":
            body, n_consts, n_carry = params["jaxpr"], params["num_consts"], params["num_carry"]

            def step(carry, xs, body=body, held=x[:n_consts], n_carry=n_carry):
                out = _eval_rounding(body.jaxpr, body.consts, *held, *carry, *xs)
                return out[:n_carry], out[n_carry:]

            carry, ys = lax.scan(step, x[n_consts:n_consts + n_carry], x[n_consts + n_carry:],
                                 length=params["length"], reverse=params["reverse"])
            out = [*carry, *ys]
        elif any(key in params for key in ("jaxpr", "call_jaxpr")):
            sub = params.get("jaxpr", params.get("call_jaxpr"))
            out = _eval_rounding(getattr(sub, "jaxpr", sub), getattr(sub, "consts", ()), *x)
        else:
            out = eqn.primitive.bind(*x, **params)
            out = out if eqn.primitive.multiple_results else [out]
        env.update(zip(eqn.outvars, out))
    return [read(var) for var in jaxpr.outvars]


def _rounding_products(fn):
    """``fn`` as the TPU's default precision runs it: each product's operands
    rounded to bfloat16 (the CPU's products are exact at any precision)."""
    def run(*args):
        closed, shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        flat = _eval_rounding(closed.jaxpr, closed.consts, *jax.tree.leaves(args))
        return jax.tree.unflatten(jax.tree.structure(shape), flat)
    return run


def test_bfloat16_operands_round_what_the_tpu_path_rounds():
    """Output and gradients with bfloat16 operands against XLA's path with
    every product's operands rounded to bfloat16: the kernels round where
    XLA's default precision does and nowhere else, so they stand a hundred
    times nearer to it than either stands to the exact products."""
    (q, k, v, g, beta), ct = _inputs("keys-alike")
    q_rep, k_rep = _repeated(q, k)
    per_key = HV // HK

    def xla_both(*a):
        o, vjp = jax.vjp(_xla, *a)
        dq, dk, *rest = vjp(ct)
        return (o, *(x.reshape(R, T, HK, per_key, D).sum(axis=3) for x in (dq, dk)), *rest)

    def kernel_both(operands):
        def run(*a):
            o, vjp = jax.vjp(lambda *b: _kernel(*b, operands=operands), *a)
            return (o, *vjp(ct))
        return run

    with jax.default_matmul_precision("highest"):
        exact = jax.jit(xla_both)(q_rep, k_rep, v, g, beta)
        rounded = jax.jit(_rounding_products(xla_both))(q_rep, k_rep, v, g, beta)
        got = jax.jit(kernel_both(jnp.bfloat16))(q, k, v, g, beta)
    for name, x_got, x_rounded, x_exact in zip(("o",) + NAMES, got, rounded, exact):
        far = _rel(x_rounded, x_exact)
        assert far > 1e-3, (name, far)  # the rounding is there to see
        assert _rel(x_got, x_rounded) < far / 100, (name, _rel(x_got, x_rounded), far)


def _layer(kernel, **kw):
    return gated_delta.GatedDeltaNet(**{
        "n_key_heads": HK, "n_value_heads": HV, "key_dim": D, "value_dim": D, "conv_width": 4,
        "chunk": 8, "rms_eps": 1e-6, "kernel": kernel, **kw})


def test_the_layer_on_the_kernel_is_the_layer_on_xlas_path():
    """Output, mean decay and the gradients of every weight and of the input,
    over two row groups of 32 tokens in chunks of 8."""
    h = jax.random.normal(jax.random.key(4), (4, 32, 64))
    params = _layer(False).init(jax.random.key(5), h)["params"]
    params = {name: 3 * w if w.ndim == 2 else w for name, w in params.items()}

    def run(layer):
        def loss(params, h):
            out, decay = layer.apply({"params": params}, h)
            return jnp.sum(jnp.sin(out)), (out, decay)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(params, h)

    assert _layer(True).kernel_reason(32) is None
    with jax.default_matmul_precision("highest"):
        (_, (want, want_decay)), want_grads = run(_layer(False))
        (_, (got, got_decay)), got_grads = run(_layer(True))
    assert _rel(got - h, want - h) < 1e-5 and float(got_decay) == pytest.approx(float(want_decay))
    flat = jax.tree_util.tree_leaves_with_path(got_grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.linalg.norm(w)) > 0, jax.tree_util.keystr(path)
        assert _rel(g, w) < 5e-5, (jax.tree_util.keystr(path), _rel(g, w))


def _pallas_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("kernel,calls", [(False, 0), (True, 3)], ids=["xla", "kernel"])
def test_the_layers_gradient_holds_the_kernels_only_where_it_is_told(kernel, calls):
    """The forward, the row group's recomputed forward with the chunks'
    states, and the backward; on the kernel's path no copy of the key heads
    to the value heads (``[rows, T, HK, HV / HK, D]``)."""
    layer = _layer(kernel)
    h = jax.ShapeDtypeStruct((2, 32, 64), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(jax.random.key(0), jnp.zeros((2, 32, 64))))

    def loss(params, h):
        return jnp.sum(layer.apply(params, h)[0])

    text = str(jax.make_jaxpr(jax.grad(loss))(params, h))
    assert text.count("pallas_call") == calls
    assert (f"f32[2,32,{HK},{HV // HK},{D}]" in text) is not kernel


# ------------------------------------------------------------- the predicate


@pytest.mark.parametrize("attrs,tokens,why", [
    ({}, 4096, None),
    ({"dtype": jnp.bfloat16}, 4096, "compute dtype bfloat16"),
    ({"key_dim": 8, "value_dim": 8}, 16, "head widths 8 / 8 are not multiples of 128 lanes"),
    ({"n_key_heads": 3}, 4096, "32 value heads do not group over 3 key heads"),
    ({}, 4004, "chunks of 4004 do not cut 4004 tokens into multiples of 8"),
    ({"chunk": 128, "key_dim": 256, "value_dim": 256}, 4096, "of VMEM a step (budget 14)"),
], ids=["the-cell", "bf16", "tiny-widths", "heads-do-not-group", "row-not-cut", "over-vmem"])
def test_kernel_reason_says_why(attrs, tokens, why):
    """The real preset's layer at float32 takes the kernel pair at the cell's
    4,096 tokens; each of the others says why it does not."""
    spec = token_encoder.TOKEN_ENCODERS["qwen3-next-80b-a3b-ep32"]
    layer = gated_delta.GatedDeltaNet(**{**token_encoder.delta_attrs(spec, jnp.float32, True),
                                        **attrs})
    reason = layer.kernel_reason(tokens)
    assert reason is None if why is None else why in reason, reason


def _cfg(**kw):
    return config_lib.SupConConfig(**{
        "model": "qwen3-next-80b-a3b-ep32", "dataset": "synthetic", "batch_size": 4,
        "size": 1024, "epochs": 1, "learning_rate": 0.001, "method": "SimCLR", "remat": True,
        **kw})


# where the convolution's kernel pair gives another reason than the rule's
CONV_WHY = {"tiny-by-shape": "64 channels are not a multiple of 128 lanes"}


@pytest.mark.parametrize("case,cfg_kw,n_devices,backend,engaged,why", [
    ("the-cell-on-one-tpu", {}, 1, "tpu", 3, None),
    ("tiny-by-shape", {"model": "qwen3-next-tiny", "size": 16}, 1, "tpu", 0,
     "head widths 8 / 8 are not multiples of 128 lanes"),
    ("cpu", {}, 1, "cpu", 0, "non-TPU backend (cpu)"),
    ("bf16", {"bf16": True}, 1, "tpu", 0, "compute dtype bfloat16"),
    ("two-devices", {}, 2, "tpu", 0, "2 devices in the mesh"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_linear_plan_says_which_layers_take_the_kernel_pair_and_why(
        monkeypatch, case, cfg_kw, n_devices, backend, engaged, why):
    """No flag: the backend, the mesh, the dtype and the layer's shape
    decide, and the run says so once (one ``linear_attention_plan`` event on
    track ``compile``; 3 / 0 for the benchmark's cell on a TPU), for the
    rule's kernel pair (``engaged`` / ``on_xla``, a layer's ``path`` and
    ``reason``) and for the convolution's (``conv_engaged`` /
    ``conv_on_xla``, ``conv_path`` and ``conv_reason``)."""
    cfg = _cfg(**cfg_kw)
    monkeypatch.setattr(supcon.jax, "default_backend", lambda: backend)
    rec = tracing.FlightRecorder(clock=lambda: 0.0)
    tracing.install(rec)
    try:
        plan = supcon.plan_linear_attention(
            cfg, n_devices, dtype=jnp.bfloat16 if cfg.bf16 else jnp.float32, remat=cfg.remat)
    finally:
        tracing.uninstall()
    (event,) = [r for r in rec.snapshot() if r["name"] == "linear_attention_plan"]
    said = event["args"]
    assert event["track"] == "compile" and [p["name"] for p in plan] == ["block0", "block1",
                                                                       "block2"]
    assert (said["engaged"], said["on_xla"]) == (engaged, 3 - engaged)
    assert [p["reason"] for p in plan] == [p["reason"] for p in said["per_layer"]] == [why] * 3
    assert [p["path"] for p in said["per_layer"]] == ["xla" if why else "kernel"] * 3
    conv_why = CONV_WHY.get(case, why)
    conv_engaged = 0 if conv_why else 3
    assert (said["conv_engaged"], said["conv_on_xla"]) == (conv_engaged, 3 - conv_engaged)
    assert ([p["conv_reason"] for p in plan] == [p["conv_reason"] for p in said["per_layer"]]
            == [conv_why] * 3)
    assert [p["conv_path"] for p in said["per_layer"]] == ["xla" if conv_why else "kernel"] * 3


def test_an_encoder_without_linear_layers_has_no_linear_plan():
    for model, size in (("moonlight-16b-a3b-ep8", 1024), ("resnet50", 32)):
        assert supcon.plan_linear_attention(_cfg(model=model, size=size), 1) == []
