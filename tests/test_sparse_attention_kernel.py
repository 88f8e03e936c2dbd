"""Sparse attention's kernel pair (ops/sparse_attention.py) against XLA's
path (models/sparse_attention._attend_selected / _attend_chunk).

CPU, Pallas interpret mode: correctness only. With float32 ``operands`` both
sides multiply the same numbers (the inputs are rounded to bfloat16 first,
XLA's CPU products and the interpreter's are exact), so the kernels' own
arithmetic is held to 1e-5 of each result's norm; with the TPU's bfloat16
operands the difference is the probabilities' rounding, a bfloat16's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_pytorch_distributed_tpu import config as config_lib
from simclr_pytorch_distributed_tpu.models import sparse_attention as msa
from simclr_pytorch_distributed_tpu.models import token_encoder
from simclr_pytorch_distributed_tpu.ops import sparse_attention as sa
from simclr_pytorch_distributed_tpu.train import supcon
from simclr_pytorch_distributed_tpu.utils import tracing

R, T, H, G, D, J, DI, Q_CHUNK = 2, 512, 8, 2, 128, 2, 8, 256
NAMES = ("o", "target", "dq", "dk", "dv")


def _rounded(t):
    return t.astype(jnp.bfloat16).astype(jnp.float32)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _inputs(seed=0, rows=R, tokens=T):
    ks = jax.random.split(jax.random.key(seed), 7)
    return dict(
        q=_rounded(jax.random.normal(ks[0], (rows, tokens, H, D))),
        k=_rounded(jax.random.normal(ks[1], (rows, tokens, G, D))),
        v=_rounded(jax.random.normal(ks[2], (rows, tokens, G, D))),
        qi=jax.random.normal(ks[3], (rows, tokens, J, DI)),
        ki=jax.random.normal(ks[4], (rows, tokens, DI)),
        wi=jax.random.normal(ks[5], (rows, tokens, J)),
        do=jax.random.normal(ks[6], (rows, tokens, H * D)),
    )


def _chosen(x, topk):
    """``[R, T, T]`` (queries, keys): the chunks' selections side by side, as
    ``sparse_attention_kernel`` lays them."""
    T, rows = x["q"].shape[1], []
    for first, last in msa._chunks(T, Q_CHUNK):
        _, chosen = jax.vmap(lambda qi, ki, wi: msa._select(qi, ki, wi, first, topk))(
            x["qi"][:, first:last], x["ki"][:, :last], x["wi"][:, first:last])
        rows.append(jnp.pad(chosen, ((0, 0), (0, 0), (0, T - last))))
    return jnp.concatenate(rows, axis=1)


def _kernel(q, k, v, chosen, operands=jnp.float32):
    """The kernel pair in the model's layouts: ``(o [R, T, H*D], target [R,
    T, T])``."""
    R, T = q.shape[:2]
    o_t, target_t = sa.attend(
        q.transpose(0, 2, 3, 1).reshape(R, H * D, T), k.reshape(R, T, G * D),
        v.reshape(R, T, G * D), chosen.astype(jnp.int8).swapaxes(1, 2),
        n_heads=H, operands=operands, interpret=True)
    return o_t.swapaxes(1, 2), target_t.swapaxes(1, 2)


def _oracle(q, k, v, chosen):
    """``_attend_selected``, a chunk of queries and a row at a time as
    ``_attend_chunk`` calls it."""
    outs, targets = [], []
    for first, last in msa._chunks(T, Q_CHUNK):
        o, target = jax.vmap(msa._attend_selected)(
            q[:, first:last], k[:, :last], v[:, :last], chosen[:, first:last, :last])
        outs.append(o)
        targets.append(jnp.pad(target, ((0, 0), (0, 0), (0, T - last))))
    return jnp.concatenate(outs, axis=1), jnp.concatenate(targets, axis=1)


def _with_cotangents(fn, x, chosen):
    (o, target), vjp = jax.vjp(lambda q, k, v: fn(q, k, v, chosen), x["q"], x["k"], x["v"])
    return dict(zip(NAMES, (o, target) + vjp((x["do"], jnp.zeros_like(target)))))


@pytest.fixture(scope="module", params=[100, 1000], ids=["topk-bites", "topk-over-keys"])
def both(request):
    """Kernel pair and oracle on the same inputs: ``topk`` 100 bites from the
    101st token on, 1000 is more than a row has keys."""
    x = _inputs()
    chosen = _chosen(x, request.param)
    per_query = jnp.sum(chosen, axis=-1)
    assert int(per_query.max()) == min(request.param, T) and int(per_query.min()) == 1
    return _with_cotangents(_kernel, x, chosen), _with_cotangents(_oracle, x, chosen)


@pytest.mark.parametrize("name", NAMES)
def test_kernel_matches_attend_selected(both, name):
    """Two query blocks forward and two chunk sweeps, so the target, ``dk``
    and ``dv`` are accumulated over heads, blocks and chunks."""
    got, want = both
    assert got[name].shape == want[name].shape
    assert _rel(got[name], want[name]) < 1e-5, _rel(got[name], want[name])


def _rounded_oracle(x, chosen, delta_from_o):
    """The same mathematics in plain jnp with every rounding of the TPU's
    path written out: ``q``, ``k``, ``v``, ``dO``, the probabilities and
    ``dS / sqrt(d)`` enter their products as bfloat16, everything else is
    float32. ``delta_from_o``: the softmax's backward takes ``rowsum(dO * O)``
    from the rounded products' output, as the kernel does, and not ``sum(P *
    dP)`` from the unrounded probabilities, as XLA's transpose does."""
    bf = lambda t: t.astype(jnp.bfloat16)  # noqa: E731
    f32 = dict(preferred_element_type=jnp.float32)
    R, T = x["q"].shape[:2]
    q, do = (t.reshape(R, T, G, H // G, D) for t in (x["q"], x["do"]))
    k, v = x["k"], x["v"]
    s = jnp.einsum("rqghd,rsgd->rghqs", bf(q), bf(k), **f32) / np.sqrt(D)
    p = jax.nn.softmax(jnp.where(chosen[:, None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("rghqs,rsgd->rqghd", bf(p), bf(v), **f32)
    dp = jnp.einsum("rqghd,rsgd->rghqs", bf(do), bf(v), **f32)
    if delta_from_o:
        delta = jnp.sum(bf(do).astype(jnp.float32) * o, axis=-1).transpose(0, 2, 3, 1)[..., None]
    else:
        delta = jnp.sum(p * dp, axis=-1, keepdims=True)
    ds = bf(p * (dp - delta) / np.sqrt(D))
    return dict(
        o=o.reshape(R, T, H * D), target=jnp.sum(p, axis=(1, 2)) / H,
        dq=jnp.einsum("rghqs,rsgd->rqghd", ds, bf(k), **f32).reshape(x["q"].shape),
        dk=jnp.einsum("rghqs,rqghd->rsgd", ds, bf(q), **f32),
        dv=jnp.einsum("rghqs,rqghd->rsgd", bf(p), bf(do), **f32))


@pytest.fixture(scope="module")
def on_bfloat16():
    x = _inputs(seed=1)
    chosen = _chosen(x, 100)
    got = _with_cotangents(lambda *a: _kernel(*a, operands=jnp.bfloat16), x, chosen)
    return got, _rounded_oracle(x, chosen, True), _rounded_oracle(x, chosen, False)


@pytest.mark.parametrize("name", NAMES)
def test_bfloat16_operands_round_what_the_tpus_path_rounds(on_bfloat16, name):
    """The TPU's operands. Against the oracle that rounds the same numbers
    the kernels are within 1e-4 of every result's norm. Against XLA's own
    transpose of the softmax, ``sum(P * dP)`` in the place of ``rowsum(dO *
    O)``, forward results and ``dv`` are as close and ``dq``, ``dk`` a
    bfloat16's rounding apart (1.5e-3 on the chip at the cell's size,
    PERF.md section 6, PR 29): ``O`` carries the rounded probabilities."""
    got, same_delta, xlas_delta = on_bfloat16
    assert _rel(got[name], same_delta[name]) < 1e-4, _rel(got[name], same_delta[name])
    assert _rel(got[name], xlas_delta[name]) < (5e-3 if name in ("dq", "dk") else 1e-4)


@pytest.mark.parametrize("operands", [jnp.float32, jnp.bfloat16], ids=lambda d: d.__name__)
def test_a_masked_key_contributes_exactly_nothing(operands):
    x = _inputs(seed=2)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (R, T, T))
    never = 37  # no query selects this key, its own query neither
    chosen = causal.at[:, :, never].set(False)
    got = _with_cotangents(lambda *a: _kernel(*a, operands=operands), x, chosen)
    assert not np.any(np.asarray(got["target"][:, :, never]))
    dk, dv = (np.asarray(got[n]).reshape(R, T, G, D) for n in ("dk", "dv"))
    assert not np.any(dk[:, never]) and not np.any(dv[:, never])
    assert np.any(dk[:, never + 1]) and np.any(dv[:, never + 1])
    assert not np.any(np.where(np.asarray(chosen), 0.0, np.asarray(got["target"])))
    np.testing.assert_allclose(np.asarray(got["target"]).sum(-1), 1.0, rtol=1e-5)


def test_the_kernels_never_read_a_key_after_the_block():
    """Keys of the row's second half poisoned with NaN: queries of the first
    half (whole query blocks and one chunk of keys before the poison) read
    none of them, forward or backward."""
    x = _inputs(seed=3, rows=1, tokens=2048)
    chosen = _chosen(x, 100)
    half = sa.key_chunk(2048, sa.KEY_CHUNK)  # the wider sweep, the forward's
    assert half == 1024
    poison = lambda t: t.at[:, half:].set(jnp.nan)  # noqa: E731
    clean = _with_cotangents(_kernel, x, chosen)
    dirty = _with_cotangents(_kernel, dict(x, k=poison(x["k"]), v=poison(x["v"])), chosen)
    for name in ("o", "target", "dq"):
        np.testing.assert_array_equal(np.asarray(dirty[name][:, :half]),
                                      np.asarray(clean[name][:, :half]), err_msg=name)
    assert np.all(np.isnan(np.asarray(dirty["o"][:, half:])))


def test_layer_on_the_kernel_pair_is_the_layer_on_xlas_path():
    """``SparseAttention`` with ``kernel`` set, output and every gradient,
    the indexer's through the KL among them, against the same layer on XLA's
    path: apart by the kernel's bfloat16 operands only."""
    attrs = dict(n_heads=H, n_kv_heads=G, head_dim=D, index_heads=J, index_dim=DI, topk=100,
                 q_chunk=Q_CHUNK, rope_theta=1e4, mrope_section=(16, 24, 24))
    grid = 16  # 256 tokens: one backward block, two forward blocks
    h = jax.random.normal(jax.random.key(4), (R, grid * grid, 64))
    plain = msa.SparseAttention(**attrs)
    params = plain.init(jax.random.key(5), h)["params"]
    # weights large enough that the attention is far from uniform
    params = jax.tree.map(lambda p: p * 8 if p.ndim == 2 else p, params)

    def loss(layer):
        def f(params, h):
            out, kl = layer.apply({"params": params}, h)
            return jnp.sum(jnp.square(out)) + kl
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(params, h)

    assert msa.SparseAttention(**attrs, kernel=True).kernel_reason(grid * grid) is None
    want, want_grads = loss(plain)
    got, got_grads = loss(msa.SparseAttention(**attrs, kernel=True))
    assert float(got) == pytest.approx(float(want), rel=1e-3)
    flat = jax.tree_util.tree_leaves_with_path(got_grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert _rel(g, w) < 2e-2, (jax.tree_util.keystr(path), _rel(g, w))


# ------------------------------------------------------------- the predicate


def _cfg(**kw):
    return config_lib.SupConConfig(**{
        "model": "keye-vl2-a3b-ep8", "dataset": "synthetic", "batch_size": 4, "size": 1024,
        "epochs": 1, "learning_rate": 0.001, "method": "SimCLR", "remat": True, **kw})


def _plan(monkeypatch, cfg, n_devices, backend):
    monkeypatch.setattr(supcon.jax, "default_backend", lambda: backend)
    rec = tracing.FlightRecorder(clock=lambda: 0.0)
    tracing.install(rec)
    try:
        plan = supcon.plan_sparse_attention(
            cfg, n_devices, dtype=jnp.bfloat16 if cfg.bf16 else jnp.float32, remat=cfg.remat)
    finally:
        tracing.uninstall()
    events = [r for r in rec.snapshot() if r["name"] == "sparse_attention_plan"]
    return plan, events


@pytest.mark.parametrize("case,cfg_kw,n_devices,backend,engaged,why", [
    ("the-cell-on-one-tpu", {}, 1, "tpu", 5, None),
    ("tiny-by-shape", {"model": "keye-vl2-tiny", "size": 16}, 1, "tpu", 0, "head_dim 8"),
    ("cpu", {}, 1, "cpu", 0, "non-TPU backend (cpu)"),
    ("bf16", {"bf16": True}, 1, "tpu", 0, "bfloat16"),
    ("two-devices", {}, 2, "tpu", 0, "2 devices in the mesh"),
    ("rows-too-long", {"size": 1280}, 1, "tpu", 0, "MiB of VMEM"),
    ("rows-do-not-tile", {"size": 1040}, 1, "tpu", 0, "do not cut into blocks"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_plan_says_which_layers_take_the_kernel_pair_and_why(
        monkeypatch, case, cfg_kw, n_devices, backend, engaged, why):
    """No flag: the backend, the mesh, the dtype and the row's shape decide,
    and the run says so once (one ``sparse_attention_plan`` event on track
    ``compile``; 5 / 0 for the benchmark's cell on a TPU)."""
    cfg = _cfg(**cfg_kw)
    plan, events = _plan(monkeypatch, cfg, n_devices, backend)
    layers = token_encoder.TOKEN_ENCODERS[cfg.model].layers
    assert [layer["name"] for layer in plan] == [f"block{k}" for k in range(layers)]
    assert len(events) == 1 and events[0]["track"] == "compile"
    said = events[0]["args"]
    assert (said["engaged"], said["on_xla"]) == (engaged, layers - engaged)
    assert sum(layer["reason"] is None for layer in plan) == engaged
    if why is None:
        assert said["reasons"] == {}
    else:
        assert list(said["reasons"].values()) == [[f"block{k}" for k in range(layers)]]
        assert why in next(iter(said["reasons"])), said["reasons"]


def test_a_resnet_has_no_attention_plan(monkeypatch):
    plan, events = _plan(monkeypatch, _cfg(model="resnet50", size=32), 1, "tpu")
    assert plan == [] and events == []


@pytest.mark.parametrize("dtype,size,why", [
    (jnp.float32, 1024, None),
    (jnp.bfloat16, 1024, "bfloat16"),
    (jnp.float32, 1040, "do not cut into blocks"),
    (jnp.float32, 1280, "17.2 MiB of VMEM"),
], ids=lambda v: str(v))
def test_kernel_reason_is_the_plans_and_the_layers(dtype, size, why):
    """One predicate: what the plan says of a layer is what its ``__call__``
    asks."""
    spec = token_encoder.TOKEN_ENCODERS["keye-vl2-a3b-ep8"]
    layer = msa.SparseAttention(**token_encoder.attention_attrs(spec, dtype, True))
    reason = layer.kernel_reason((size // spec.patch) ** 2)
    assert reason is None if why is None else why in reason
    plan = token_encoder.attention_plan("keye-vl2-a3b-ep8", size, dtype=dtype)
    assert [p["reason"] for p in plan] == [reason] * spec.layers
    assert token_encoder.attention_plan("keye-vl2-a3b-ep8", size, "owner")[0] == {
        "name": "block0", "reason": "owner"}


def _pallas_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("kernel,calls", [(False, 0), (True, 3)], ids=["xla", "kernel"])
def test_the_layers_gradient_holds_the_kernels_only_where_it_is_told(kernel, calls):
    """Forward, the row group's recomputed forward, and the backward; the
    tiny preset keeps XLA's path even when told (head_dim 8)."""
    spec = token_encoder.TOKEN_ENCODERS["keye-vl2-a3b-ep8"]
    attrs = dict(token_encoder.attention_attrs(spec, jnp.float32, kernel), index_heads=1)
    layer = msa.SparseAttention(**attrs)
    h = jax.ShapeDtypeStruct((2, 256, 64), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(jax.random.key(0), jnp.zeros((2, 256, 64))))

    def loss(params, h):
        out, kl = layer.apply(params, h)
        return jnp.sum(out) + kl

    assert _pallas_calls(jax.grad(loss), params, h) == calls
    tiny = token_encoder.TOKEN_ENCODERS["keye-vl2-tiny"]
    small = msa.SparseAttention(**token_encoder.attention_attrs(tiny, jnp.float32, True))
    hs = jax.ShapeDtypeStruct((2, 16, 32), jnp.float32)
    ps = jax.eval_shape(lambda: small.init(jax.random.key(0), jnp.zeros((2, 16, 32))))
    assert _pallas_calls(jax.grad(lambda p, x: sum(map(jnp.sum, small.apply(p, x)))), ps, hs) == 0
