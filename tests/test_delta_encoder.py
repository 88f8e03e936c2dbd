"""The token encoder's third block (Qwen3-Next-80B-A3B's: three Gated DeltaNet
layers to one gated full-attention layer, softmax-routed experts beside a
shared expert that a sigmoid gates) against its plain reference
(benchmark/reference_delta.py), at the tiny preset on the CPU with seeded
weights; the chunked delta rule against the recurrence token by token; the
short convolution, the gated attention and the expert share on their own.
"""

import os
import sys
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import adapter_delta  # noqa: E402
import reference_delta  # noqa: E402

from simclr_pytorch_distributed_tpu import config as config_lib  # noqa: E402
from simclr_pytorch_distributed_tpu.models import (  # noqa: E402
    TOKEN_ENCODERS,
    SupConResNet,
    build_encoder,
    experts,
    gated_attention,
    gated_delta,
    infer_architecture_from_variables,
    token_encoder,
)

TINY = "qwen3-next-tiny"
REAL = "qwen3-next-80b-a3b-ep32"


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ------------------------------------------------- the chunked delta rule


def delta_inputs(decay: float, beta_bias: float, tokens=16, seed=0):
    """``q``, ``k`` L2-normed (``q`` scaled), ``v`` normal, ``g`` uniform in
    ``(-decay, 0)``, ``beta`` sigmoid of ``beta_bias`` plus noise; keys made
    alike within a run of tokens, as a flat image tile's are."""
    k = jax.random.split(jax.random.key(seed), 6)
    R, H, dk, dv = 2, 3, 8, 6
    base = jax.random.normal(k[0], (R, 1, H, dk))
    keys = gated_delta.l2_normalise(base + 0.3 * jax.random.normal(k[1], (R, tokens, H, dk)))
    q = gated_delta.l2_normalise(jax.random.normal(k[2], (R, tokens, H, dk))) / np.sqrt(dk)
    v = jax.random.normal(k[3], (R, tokens, H, dv))
    g = -decay * jax.random.uniform(k[4], (R, tokens, H))
    beta = jax.nn.sigmoid(beta_bias + jax.random.normal(k[5], (R, tokens, H)))
    return q, keys, v, g, beta


@pytest.mark.parametrize("decay,beta_bias", [(0.2, 0.0), (20.0, 0.0), (2.0, 8.0)],
                         ids=["mild", "strong-decay", "beta-near-1"])
@pytest.mark.parametrize("chunk", [1, 2, 4, 8, 16])
def test_chunked_rule_is_the_recurrence(chunk, decay, beta_bias):
    """Outputs and the gradients of all five inputs against the token-by-token
    recurrence: strong decay (``g`` to -20 a token: a chunk's decay passes
    ``exp(-88)``, where ``exp(-G)`` alone would overflow) and ``beta`` near 1
    included."""
    inputs = delta_inputs(decay, beta_bias)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(3 * fn(*a)))  # noqa: E731
    chunked = lambda *a: gated_delta.chunked_delta_rule(*a, chunk)  # noqa: E731
    both = lambda fn: jax.jit(lambda *a: (fn(*a), jax.grad(loss(fn), argnums=range(5))(*a)))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want, grads_want = both(jax.vmap(reference_delta.delta_rule))(*inputs)
        got, grads_got = both(chunked)(*inputs)
    assert bool(jnp.all(jnp.isfinite(got))) and float(jnp.linalg.norm(want)) > 0.1
    assert rel(got, want) < 2e-5
    for g_got, g_want in zip(grads_got, grads_want):
        assert bool(jnp.all(jnp.isfinite(g_got)))
        assert rel(g_got, g_want) < 5e-5


def test_unit_lower_inverse_and_its_gradient():
    n = jnp.tril(jax.random.normal(jax.random.key(0), (3, 12, 12)), -1) * 0.4
    with jax.default_matmul_precision("highest"):
        inverse = gated_delta.unit_lower_inverse(n)
        np.testing.assert_allclose(inverse @ (jnp.eye(12) + n), jnp.broadcast_to(jnp.eye(12), n.shape),
                                   atol=1e-5)
        w = jax.random.normal(jax.random.key(1), n.shape)
        got = jax.grad(lambda n: jnp.sum(w * gated_delta.unit_lower_inverse(n)))(n)
        want = jax.grad(lambda n: jnp.sum(w * jnp.linalg.inv(jnp.eye(12) + n)))(n)
    np.testing.assert_allclose(got, jnp.tril(want, -1), atol=1e-4 * float(jnp.max(jnp.abs(want))))


# ---------------------------------------------------------------- causality


def test_short_convolution_is_causal_and_depthwise():
    x = jax.random.normal(jax.random.key(0), (2, 10, 5))
    w = jax.random.normal(jax.random.key(1), (4, 5))
    y = gated_delta.short_conv(x, w)
    want = np.zeros(x.shape)
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(w[j]) * np.asarray(x[:, t - 3 + j])
    np.testing.assert_allclose(y, want, atol=1e-5)
    moved = gated_delta.short_conv(x.at[:, 6].add(1.0), w)
    np.testing.assert_array_equal(moved[:, :6], y[:, :6])
    assert float(jnp.min(jnp.abs(moved[:, 6:] - y[:, 6:]).sum(-1)[:, :4])) > 0


class Stack(nn.Module):
    """The preset's blocks over tokens, without the patch embedding and the
    pooling."""

    spec: Any

    @nn.compact
    def __call__(self, h):
        for k in range(self.spec.layers):
            h, *_ = token_encoder.Block(self.spec, index=k, name=f"block{k}")(h, False)
        return h


def test_every_layer_of_the_stack_is_causal():
    spec = TOKEN_ENCODERS[TINY]
    h = jax.random.normal(jax.random.key(0), (2, 16, spec.hidden))
    stack = Stack(spec)
    params = stack.init(jax.random.key(1), h)
    apply = jax.jit(stack.apply)
    out = apply(params, h)
    for t in (3, 9):
        moved = apply(params, h.at[:, t].add(1.0))
        np.testing.assert_array_equal(moved[:, :t], out[:, :t])
        assert float(jnp.min(jnp.abs(moved[:, t:] - out[:, t:]).sum(-1))) > 0


# ---------------------------------------------------------- gated attention


def test_gated_attention_is_an_einsum_head_by_head():
    """Query by query and head by head in float64: the partial rotary turn
    (rotate-half over the first ``rope_dim`` dimensions), the grouped keys
    and the sigmoid gate read from the query projection."""
    spec = TOKEN_ENCODERS[TINY]
    layer = gated_attention.GatedAttention(**token_encoder.gated_attrs(spec, jnp.float32))
    h = jax.random.normal(jax.random.key(2), (3, 16, spec.hidden))
    params = layer.init(jax.random.key(5), h)["params"]
    params = {name: (1.0 + 0.1 * jax.random.normal(jax.random.key(i), w.shape)
                     if "norm" in name else 8 * w) for i, (name, w) in enumerate(params.items())}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(layer.apply({"params": params}, h) - h, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    H, G, d, r = spec.n_heads, spec.n_kv_heads, spec.head_dim, spec.rope_dim
    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True) + spec.rms_eps) * g  # noqa: E731

    def turn(x, t):
        out, half = x.copy(), r // 2
        for m in range(half):
            angle = t * spec.rope_theta ** (-m / half)
            out[m] = x[m] * np.cos(angle) - x[m + half] * np.sin(angle)
            out[m + half] = x[m + half] * np.cos(angle) + x[m] * np.sin(angle)
        return out

    want = np.zeros_like(got)
    for i, row in enumerate(np.asarray(h, np.float64)):
        a = rms(row, p["norm"])
        q_gate = (a @ p["q"]).reshape(16, H, 2 * d)
        keys = (a @ p["k"]).reshape(16, G, d)
        values = (a @ p["v"]).reshape(16, G, d)
        out = np.zeros((16, H, d))
        for n in range(H):
            g = n // (H // G)
            ks = np.stack([turn(rms(keys[s, g], p["k_norm"]), s) for s in range(16)])
            for t in range(16):
                query = turn(rms(q_gate[t, n, :d], p["q_norm"]), t)
                logits = ks[: t + 1] @ query / np.sqrt(d)
                weights = np.exp(logits - logits.max())
                o = (weights / weights.sum()) @ values[: t + 1, g]
                out[t, n] = o / (1.0 + np.exp(-q_gate[t, n, d:]))
        want[i] = out.reshape(16, H * d) @ p["o"]
    assert np.linalg.norm(want) > 1e-2
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


# ------------------------------------------------ program against reference


@pytest.fixture(scope="module")
def both_sides():
    """Loss, gradients and the statistics after one train-mode forward of the
    program and of the reference, on weights moved off their initial
    symmetry (norms not 1, matrices three times their deviation so that the
    router chooses firmly and the delta rule's corrections are not small)."""
    with jax.default_matmul_precision("highest"):
        model = SupConResNet(model_name=TINY, remat=True)
        views = jax.random.normal(jax.random.key(4), (6, 16, 16, 3))
        shape = jax.eval_shape(lambda: model.init(jax.random.key(0), views, train=True))
        key = jax.random.key(3)
        ref_params = {
            name: (w + 0.1 * jax.random.normal(jax.random.fold_in(key, 99 + i), w.shape)
                   if "norm" in name or name.endswith(("A_log", "dt_bias", "conv")) else 3 * w)
            for i, (name, w) in enumerate(sorted(
                reference_delta.init_params(key, TINY, 128).items()))}
        params = adapter_delta.to_program(ref_params, shape["params"])
        stats0 = jax.tree.map(jnp.zeros_like, shape["batch_stats"])

        def program(p, stats):
            feats, mutated = model.apply({"params": p, "batch_stats": stats}, views,
                                         train=True, mutable=["batch_stats", "aux"])
            aux_loss, metrics = model.read_aux(mutated["aux"])
            return jnp.sum(jnp.sin(feats)) + aux_loss, (mutated["batch_stats"], metrics)

        def reference(p):
            feats, aux_loss, stats = reference_delta.forward(p, views, TINY)
            return jnp.sum(jnp.sin(feats)) + aux_loss, stats

        (loss_p, (stats_p, metrics)), grads_p = jax.jit(
            jax.value_and_grad(program, has_aux=True))(params, stats0)
        (loss_r, stats_r), grads_r = jax.jit(
            jax.value_and_grad(reference, has_aux=True))(ref_params)
        running = {k: 0.1 * v for k, v in stats_r.items()}  # one step from rest
    return {"loss": (float(loss_p), float(loss_r)),
            "grads": (adapter_delta.to_reference(grads_p), grads_r),
            "stats": (adapter_delta.to_reference(stats_p), running), "metrics": metrics}


def test_loss_agrees_with_the_reference(both_sides):
    program, reference = both_sides["loss"]
    assert abs(program - reference) <= 1e-5 * abs(reference)


@pytest.mark.parametrize("name", sorted(reference_delta.param_spec(TINY)))
def test_gradient_leaf_agrees_with_the_reference(both_sides, name):
    program, reference = both_sides["grads"]
    assert float(jnp.linalg.norm(reference[name])) > 0
    assert rel(program[name], reference[name]) <= 1e-4


@pytest.mark.parametrize("name", reference_delta.running_names(TINY))
def test_running_statistic_agrees_with_the_reference(both_sides, name):
    program, reference = both_sides["stats"]
    np.testing.assert_allclose(program[name], reference[name], atol=1e-7)
    assert float(jnp.sum(reference[name])) == pytest.approx(0.1, abs=1e-6)  # shares of a whole


def test_ring_columns_read_the_routing_and_the_decay(both_sides):
    m = both_sides["metrics"]
    assert set(m) == set(TOKEN_ENCODERS[TINY].ring_columns) == {
        "moe_held_share", "moe_load_max_over_mean", "delta_decay_mean"}
    assert 0.0 < float(m["moe_held_share"]) < 1.0 and float(m["moe_load_max_over_mean"]) >= 1.0
    assert 0.0 < float(m["delta_decay_mean"]) < 1.0


# ---------------------------------------------------------- the expert share


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_add_up_with_the_gated_shared_expert_counted_once(shares):
    """The held experts' parts that ``shares`` chips give, with what every
    chip computes alike (the gated shared expert) counted once, against the
    uncut layer: the reference with all experts held."""
    n_experts, per_token, width, shared, d = 8, 2, 8, 12, 12
    a = dict(reference_delta.TINY, num_experts=n_experts, num_experts_per_tok=per_token,
             moe_intermediate_size=width, hidden_size=d, shared_expert_intermediate_size=shared,
             experts_held=[0, n_experts])
    attrs = dict(n_experts=n_experts, top_k=per_token, width=width, shared_width=shared,
                 shared_expert_gate=True, rms_eps=1e-6)
    h = jax.random.normal(jax.random.key(2), (3, 10, d))
    params = experts.ExpertLayer(held=(0, n_experts), **attrs).init(jax.random.key(5), h)["params"]
    params = dict(params, router=8 * params["router"], **{
        n: 20 * params[n] for n in params if n.startswith(("w_", "shared_"))})
    with jax.default_matmul_precision("highest"):
        uncut, *_ = reference_delta._experts(
            {f"l/{k}": v for k, v in dict(params, norm2=params["norm"]).items()}, "l", h, a)
        b = experts.rms_norm(h, params["norm"], 1e-6)
        only_shared = jax.nn.sigmoid(b @ params["shared_expert_gate"]) * experts.gated_mlp(
            b, *(params[n] for n in ("shared_gate", "shared_up", "shared_down")))
        per, parts, held_shares = n_experts // shares, [], []
        for share in range(shares):
            cut = dict(params, **{n: params[n][per * share: per * (share + 1)]
                                  for n in ("w_gate", "w_up", "w_down")})
            out, part = experts.ExpertLayer(held=(per * share, per), **attrs).apply(
                {"params": cut}, h)
            parts.append(out - h - only_shared)  # the held experts' part alone
            held_shares.append(float(part["held_share"]))
    assert float(jnp.linalg.norm(only_shared)) > 1e-2 < float(jnp.linalg.norm(sum(parts)))
    np.testing.assert_allclose(sum(parts) + only_shared, uncut - h, atol=2e-5)
    assert sum(held_shares) == pytest.approx(1.0)


def test_layers_without_the_gate_have_no_gate_weight():
    h = jnp.zeros((1, 4, 8))
    for gate in (False, True):
        layer = experts.ExpertLayer(n_experts=4, top_k=2, width=4, held=(0, 2), shared_width=4,
                                    shared_expert_gate=gate)
        assert ("shared_expert_gate" in layer.init(jax.random.key(0), h)["params"]) is gate


# ------------------------------------------------------------- the protocol


def test_both_new_names_are_known_everywhere(tmp_path):
    base = ["--dataset", "synthetic", "--workdir", str(tmp_path), "--batch_size", "4"]
    for name, size in ((TINY, 16), (REAL, 1024)):
        assert config_lib.parse_supcon(base + ["--model", name, "--size", str(size)]).model == name
        enc = build_encoder(name, dtype=jnp.bfloat16, remat=True, sync_bn=False)
        assert isinstance(enc, token_encoder.TokenEncoder) and enc.spec is TOKEN_ENCODERS[name]
        assert [enc.spec.attention_of(k) for k in range(4)] == ["linear"] * 3 + ["gated"]
        assert token_encoder.attention_plan(name, size) == []  # no sparse-attention layer
    for name in (TINY, REAL):  # the trees name their presets, the large one unbuilt
        model = SupConResNet(model_name=name, head="linear", feat_dim=32)
        patch = TOKEN_ENCODERS[name].patch
        v = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, patch, patch, 3))))
        assert infer_architecture_from_variables(v) == (name, "linear", 32)


def test_tree_is_named_for_the_adapter():
    model = SupConResNet(model_name=TINY)
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, 16, 16, 3))))
    encoder = v["params"]["encoder"]
    assert set(encoder["block0"]["attn"]) == {"norm", "qkvz", "ba", "conv", "A_log", "dt_bias",
                                              "out_norm", "o"}
    assert set(encoder["block3"]["attn"]) == {"norm", "q", "k", "v", "o", "q_norm", "k_norm"}
    assert "shared_expert_gate" in encoder["block3"]["moe"]
    stats = adapter_delta.to_reference(v["batch_stats"])
    assert sorted(stats) == sorted(reference_delta.running_names(TINY))
    assert reference_delta.stats_order(TINY) == [f"layer{i}/prob_mean" for i in range(4)]
    spec = jax.eval_shape(lambda: reference_delta.init_params(jax.random.key(0), TINY, 128))
    assert jax.tree.structure(adapter_delta.to_program(spec, v["params"])) == jax.tree.structure(
        v["params"])


def test_program_and_reference_state_the_same_widths():
    """``TOKEN_ENCODERS`` against the configuration's file and the
    reference's tiny preset: one table each, no third."""
    keys = {"patch": "patch_size", "hidden": "hidden_size", "layers": "num_hidden_layers",
            "full_attention_interval": "full_attention_interval",
            "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
            "head_dim": "head_dim", "rope_dim": "rotary_dim", "rope_theta": "rope_theta",
            "linear_key_heads": "linear_num_key_heads",
            "linear_value_heads": "linear_num_value_heads",
            "linear_key_dim": "linear_key_head_dim", "linear_value_dim": "linear_value_head_dim",
            "conv_width": "linear_conv_kernel_dim", "delta_chunk": "chunk_size",
            "n_experts": "num_experts", "top_k": "num_experts_per_tok",
            "expert_width": "moe_intermediate_size",
            "shared_width": "shared_expert_intermediate_size", "rms_eps": "rms_norm_eps",
            "balance_coef": "balance_coef"}
    for name in (TINY, REAL):
        spec, stated = TOKEN_ENCODERS[name], reference_delta.arch(name)
        assert {k: getattr(spec, k) for k in keys} == {k: stated[v] for k, v in keys.items()}
        assert list(spec.held) == stated["experts_held"]
        assert (spec.attention, spec.router, spec.shared_expert_gate) == ("gated", "softmax", True)
        assert spec.rope_dim == spec.head_dim // 4  # partial_rotary_factor 0.25
    real = reference_delta.arch(REAL)
    assert TOKEN_ENCODERS[REAL].capacity_factor == real["expert_capacity_factor"] == 2.0


# ------------------------------------------------------------ the train step


@pytest.fixture(scope="module")
def one_step(tmp_path_factory):
    """One update through ``train.supcon.build`` and ``make_fused_update``."""
    from simclr_pytorch_distributed_tpu import recipes as recipes_lib
    from simclr_pytorch_distributed_tpu.ops.metrics import MetricRing
    from simclr_pytorch_distributed_tpu.parallel.mesh import create_mesh
    from simclr_pytorch_distributed_tpu.train import supcon
    from simclr_pytorch_distributed_tpu.train.supcon_step import metric_keys
    from simclr_pytorch_distributed_tpu.utils import tracing

    cfg = config_lib.parse_supcon([
        "--dataset", "synthetic", "--workdir", str(tmp_path_factory.mktemp("w")), "--batch_size",
        "4", "--size", "16", "--model", TINY, "--learning_rate", "0.05", "--remat",
        "--loss_impl", "dense", "--health_freq", "0"])
    recorder = tracing.FlightRecorder()
    tracing.install(recorder)
    try:
        model, schedule, tx, state, step_cfg = supcon.build(cfg, 5, 1)
    finally:
        tracing.uninstall()
    state, recipe = recipes_lib.attach_for_config(cfg, model, state, schedule=schedule)
    ring = MetricRing(3, metric_keys(extra=recipe.metric_keys))
    mesh = create_mesh(devices=jax.devices()[:1])
    update = supcon.make_fused_update(model, tx, schedule, step_cfg,
                                      supcon.make_augment_config(cfg), mesh, state,
                                      metric_ring=ring, recipe=recipe)
    images = jax.random.randint(jax.random.key(0), (4, 16, 16, 3), 0, 255).astype(jnp.uint8)
    args = (ring.init_buffer(), images, jnp.zeros((4,), jnp.int32), jax.random.key(1))
    compiled = update.lower(state, *args).compile()
    _, buffer = compiled(state, *args)
    return {"events": recorder.snapshot(), "ring": dict(zip(ring.keys, np.asarray(buffer)[0])),
            "text": compiled.as_text()}  # op_names, whole paths


def test_build_says_what_the_layers_are(one_step):
    (plan,) = [r for r in one_step["events"] if r["name"] == "linear_attention_plan"]
    assert plan["track"] == "compile" and plan["args"] == {
        "layers": {"linear": 3, "gated": 1}, "key_heads": 2, "value_heads": 4, "key_dim": 8,
        "value_dim": 8, "conv_width": 4, "chunk": 4, "full_heads": 4, "full_kv_heads": 2,
        "full_head_dim": 8, "tokens": 16, "row_group": 2, "engaged": 0, "on_xla": 3,
        "conv_engaged": 0, "conv_on_xla": 3,
        "per_layer": [{"name": f"block{k}", "path": "xla", "reason": "non-TPU backend (cpu)",
                       "conv_path": "xla", "conv_reason": "non-TPU backend (cpu)"}
                      for k in range(3)]}
    (experts_plan,) = [r["args"] for r in one_step["events"] if r["name"] == "expert_plan"]
    assert (experts_plan["layers"], experts_plan["router"], experts_plan["shared_width"],
            experts_plan["shared_gate"]) == (4, "softmax", 16, True)
    assert experts_plan["ring_columns"] == list(TOKEN_ENCODERS[TINY].ring_columns)
    assert not [r for r in one_step["events"]
                if r["name"] in ("sparse_attention_plan", "latent_attention_plan")]


def test_step_writes_the_decay_column(one_step):
    ring = one_step["ring"]
    assert 0.0 < ring["delta_decay_mean"] < 1.0 and np.isfinite(ring["loss"])
    assert 0.0 < ring["moe_held_share"] < 1.0


@pytest.mark.parametrize("scope", [
    r"encoder/block0/attn/linear_attn/", r"encoder/block1/attn/linear_attn/[^\"]*short_conv/",
    r"encoder/block2/attn/linear_attn/[^\"]*delta_scan/", r"encoder/block3/attn/[^\"]*attn_core/",
    r"encoder/block3/moe/[^\"]*shared/", r"encoder/block0/moe/[^\"]*experts/",
    r"transpose\(jvp\(SupConResNet\)\)/encoder/block0/attn/linear_attn/[^\"]*delta_scan/"])
def test_step_names_the_new_scopes(one_step, scope):
    import re

    assert re.search(scope, one_step["text"]), scope


def test_trace_report_prints_the_plans():
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "scripts"))
    import trace_report

    span = {"name": "flush_boundary", "track": "main:flush", "ph": "X", "ts": 0.0, "dur": 1.0,
            "args": {}}
    plan = {"layers": 4, "held": 16, "first": 0, "n_experts": 512, "per_token": 10,
            "rows_per_step": 32768, "capacity_factor": 2.0, "provisioned_assignments": 20480,
            "rows_per_trip": 20480, "provisioned_trips": 1, "dense_layers": 0,
            "router": "softmax", "shared_width": 512, "shared_gate": True,
            "product_operands": "bfloat16", "product_reason": None,
            "ring_columns": ["moe_held_share", "delta_decay_mean"]}
    linear = {"layers": {"linear": 3, "gated": 1}, "key_heads": 16, "value_heads": 32,
              "key_dim": 128, "value_dim": 128, "conv_width": 4, "chunk": 64, "full_heads": 16,
              "full_kv_heads": 2, "full_head_dim": 256, "tokens": 4096, "row_group": 2,
              "engaged": 2, "on_xla": 1, "conv_engaged": 1, "conv_on_xla": 2,
              "per_layer": [{"name": "block0", "path": "kernel", "reason": None,
                             "conv_path": "kernel", "conv_reason": None},
                            {"name": "block1", "path": "kernel", "reason": None,
                             "conv_path": "xla", "conv_reason": "no conv kernel"},
                            {"name": "block2", "path": "xla", "reason": "no kernel",
                             "conv_path": "xla", "conv_reason": "no conv kernel"}]}
    events = [span,
              {"name": "expert_plan", "track": "compile", "ph": "i", "ts": 0.1, "args": plan},
              {"name": "linear_attention_plan", "track": "compile", "ph": "i", "ts": 0.1,
               "args": linear},
              {"name": "health_window", "track": "health", "ph": "i", "ts": 0.5,
               "args": {"moe_held_share": 0.031, "delta_decay_mean": 0.05, "step": 10}}]
    report = trace_report.build_report(events)
    assert report["encoder"]["linear_attention_plan"] == linear
    table = trace_report.render_table(report)
    assert "shared experts of width 512 under a sigmoid gate" in table
    assert ("linear attention: 3 Gated DeltaNet layers of 16 key / 32 value heads of 128 / 128 "
            "beside 1 full, 4-tap convolution, scan in chunks of 64 of 4096 tokens, 2 rows a "
            "group, 2 on the kernel pair, 1 on XLA's path; block2: no kernel; convolution: 1 "
            "on its kernel pair, 2 on XLA's path; block1, block2: no conv kernel") in table
    # a run recorded before the convolution had a kernel pair prints as it did
    for key in ("conv_engaged", "conv_on_xla"):
        linear.pop(key)
    for layer in linear["per_layer"]:
        layer.pop("conv_path"), layer.pop("conv_reason")
    table = trace_report.render_table(trace_report.build_report(events))
    assert "block2: no kernel\n" in table + "\n" and "convolution:" not in table
    assert "delta_decay_mean 0.05" in table
