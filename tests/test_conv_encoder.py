"""The token encoder's fourth block (LFM2-8B-A1B's: gated short convolutions
beside grouped-query attention without an output gate, a leading dense layer,
then sigmoid-routed experts under a load-correcting bias with no shared
expert) against its plain reference (benchmark/reference_conv.py), at the
tiny preset on the CPU with seeded weights; the gated convolution, the
attention layer and the expert share on their own; the presets told apart.
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

import adapter_conv  # noqa: E402
import reference_conv  # noqa: E402

from simclr_pytorch_distributed_tpu import config as config_lib  # noqa: E402
from simclr_pytorch_distributed_tpu.models import (  # noqa: E402
    TOKEN_ENCODERS,
    SupConResNet,
    build_encoder,
    experts,
    gated_attention,
    gated_conv,
    infer_architecture_from_variables,
    token_encoder,
)

TINY = "lfm2-tiny"
REAL = "lfm2-8b-a1b-ep4"
KINDS = ["conv", "full", "conv", "conv", "conv"]  # published layers 0 and 2-5


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ------------------------------------------------ program against reference


@pytest.fixture(scope="module")
def both_sides():
    """Loss, gradients and the statistics after one train-mode forward, and
    the bias after two, of the program and of the reference, on weights
    moved off their initial symmetry (norms and taps not as drawn, matrices
    three times their deviation so that the router chooses firmly)."""
    with jax.default_matmul_precision("highest"):
        model = SupConResNet(model_name=TINY, remat=True)
        views = jax.random.normal(jax.random.key(4), (6, 16, 16, 3))
        shape = jax.eval_shape(lambda: model.init(jax.random.key(0), views, train=True))
        key = jax.random.key(3)
        ref_params = {
            name: (w + 0.1 * jax.random.normal(jax.random.fold_in(key, 99 + i), w.shape)
                   if "norm" in name or name.endswith("conv") else 3 * w)
            for i, (name, w) in enumerate(sorted(
                reference_conv.init_params(key, TINY, 128).items()))}
        params = adapter_conv.to_program(ref_params, shape["params"])
        stats0 = jax.tree.map(jnp.zeros_like, shape["batch_stats"])

        def program(p, stats):
            feats, mutated = model.apply({"params": p, "batch_stats": stats}, views,
                                         train=True, mutable=["batch_stats", "aux"])
            aux_loss, metrics = model.read_aux(mutated["aux"])
            return jnp.sum(jnp.sin(feats)) + aux_loss, (mutated["batch_stats"],
                                                        dict(metrics, aux_loss=aux_loss), feats)

        def reference(p, running):
            feats, aux_loss, stats = reference_conv.forward(p, views, TINY, running)
            return jnp.sum(jnp.sin(feats)) + aux_loss, (stats, feats)

        (loss_p, (stats_p, metrics, feats_p)), grads_p = jax.value_and_grad(
            program, has_aux=True)(params, stats0)
        running0 = reference_conv.running_at_rest(ref_params)
        (loss_r, (stats_r, feats_r)), grads_r = jax.value_and_grad(
            reference, has_aux=True)(ref_params, running0)
        running1 = reference_conv.step_running(running0, stats_r, TINY, 0.1)
        # a second step on the same weights: the first step's bias now chooses
        _, (stats_p2, _, _) = program(params, stats_p)
        _, (stats_r2, _) = reference(ref_params, running1)
        running2 = reference_conv.step_running(running1, stats_r2, TINY, 0.1)
    return {"loss": (float(loss_p), float(loss_r)), "features": (feats_p, feats_r),
            "grads": (adapter_conv.to_reference(grads_p), grads_r),
            "stats": (adapter_conv.to_reference(stats_p), running1),
            "stats2": (adapter_conv.to_reference(stats_p2), running2), "metrics": metrics}


def test_features_and_loss_agree_with_the_reference(both_sides):
    """Both sides' products are exact here (``highest``): what is left is the
    order of float32 sums, 1e-6 of the features at most."""
    program, reference = both_sides["features"]
    assert float(jnp.linalg.norm(reference)) > 1e-2
    assert rel(program, reference) <= 1e-5
    program, reference = both_sides["loss"]
    assert abs(program - reference) <= 1e-5 * abs(reference)


@pytest.mark.parametrize("name", sorted(reference_conv.param_spec(TINY)))
def test_gradient_leaf_agrees_with_the_reference(both_sides, name):
    """Each leaf of the first gradient within 1e-4 of its norm: float32 sums
    in another order through five layers' backward, and a dropped gate, tap
    or norm reads O(1)."""
    program, reference = both_sides["grads"]
    assert float(jnp.linalg.norm(reference[name])) > 0
    assert rel(program[name], reference[name]) <= 1e-4


@pytest.mark.parametrize("name", reference_conv.running_names(TINY))
def test_running_statistic_agrees_with_the_reference(both_sides, name):
    """One step from rest: a tenth of the batch's means (shares of 192
    assignments, to float32's last digits), and the bias one
    ``bias_update_rate`` towards the experts under a balanced load."""
    program, reference = both_sides["stats"]
    np.testing.assert_allclose(program[name], reference[name], atol=1e-7)
    if name.endswith("route_bias"):
        assert set(np.unique(np.abs(program[name]))) <= {np.float32(0.0), np.float32(0.001)}
        assert float(jnp.max(jnp.abs(program[name]))) == pytest.approx(0.001)
    else:
        assert float(jnp.sum(reference[name])) == pytest.approx(0.1, abs=1e-6)  # shares of a whole


def test_route_bias_after_two_steps_agrees_with_the_reference(both_sides):
    program, reference = both_sides["stats2"]
    for name in reference_conv.running_names(TINY):
        np.testing.assert_allclose(program[name], reference[name], atol=1e-7)
    pushed = max(float(jnp.max(jnp.abs(program[f"layer{i}/route_bias"]))) for i in range(1, 5))
    assert pushed == pytest.approx(0.002)  # an expert pushed twice the same way


def test_ring_columns_read_the_routing(both_sides):
    m = dict(both_sides["metrics"])
    m.pop("aux_loss")
    assert tuple(m) == TOKEN_ENCODERS[TINY].ring_columns == (
        "moe_held_share", "moe_load_max_over_mean", "route_bias_max_abs")
    assert 0.0 < float(m["moe_held_share"]) < 1.0  # half of the experts held
    assert float(m["moe_load_max_over_mean"]) >= 1.0
    assert float(m["route_bias_max_abs"]) == pytest.approx(0.001)


def test_the_loss_has_no_balance_term(both_sides):
    """The configuration names none: the encoder adds nothing to the loss."""
    assert float(both_sides["metrics"]["aux_loss"]) == 0.0


# ------------------------------------------------- the gated convolution


@pytest.fixture(scope="module")
def conv_layer():
    spec = TOKEN_ENCODERS[TINY]
    layer = gated_conv.GatedShortConv(spec.conv_width, spec.rms_eps)
    h = jax.random.normal(jax.random.key(2), (3, 16, spec.hidden))
    params = layer.init(jax.random.key(5), h)["params"]
    params = {name: (1.0 + 0.1 * jax.random.normal(jax.random.key(i), w.shape)
                     if name == "norm" else (w if name == "conv" else 8 * w))
              for i, (name, w) in enumerate(params.items())}
    return spec, layer, params, h


def test_conv_mixer_is_the_equations_written_out(conv_layer):
    """Token by token and channel by channel in float64: ``[B, C, x]`` are
    the projection's columns in that order, ``u[t] = sum_j w[j] (B x)[t - 2
    + j]`` with nothing before the first token, ``y = (C u) W_o``; no bias,
    no activation."""
    spec, layer, params, h = conv_layer
    with jax.default_matmul_precision("highest"):
        got = np.asarray(layer.apply({"params": params}, h) - h, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    d, taps = spec.hidden, spec.conv_width
    assert p["bcx"].shape == (d, 3 * d) and p["conv"].shape == (taps, d)
    want = np.zeros_like(got)
    for i, row in enumerate(np.asarray(h, np.float64)):
        a = row / np.sqrt(np.mean(row * row, -1, keepdims=True) + spec.rms_eps) * p["norm"]
        proj = a @ p["bcx"]
        b, c, x = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
        y = np.zeros((16, d))
        for t in range(16):
            for j in range(taps):
                s = t - (taps - 1) + j
                if s >= 0:
                    y[t] += p["conv"][j] * b[s] * x[s]
            y[t] *= c[t]
        want[i] = y @ p["o"]
    assert np.linalg.norm(want) > 1e-2
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())
    # C's columns anywhere else make another function (B and x commute)
    b, c, x = jnp.split(params["bcx"], 3, -1)
    swapped = dict(params, bcx=jnp.concatenate([c, b, x], -1))
    with jax.default_matmul_precision("highest"):
        other = np.asarray(layer.apply({"params": swapped}, h) - h, np.float64)
    assert np.abs(other - want).max() > 0.1 * np.abs(want).max()


def test_conv_mixer_is_causal(conv_layer):
    """A later token changes no earlier output; the token itself and the two
    after it (three taps) do see it."""
    _, layer, params, h = conv_layer
    apply = jax.jit(layer.apply)
    out = apply({"params": params}, h)
    for t in (0, 6, 13):
        moved = apply({"params": params}, h.at[:, t].add(1.0))
        np.testing.assert_array_equal(moved[:, :t], out[:, :t])
        assert float(jnp.min(jnp.abs(moved[:, t:t + 3] - out[:, t:t + 3]).sum(-1))) > 0
        # beyond the taps the convolution forgets the token: only the
        # residual stream's own row t changed
        np.testing.assert_array_equal(moved[:, t + 3:], out[:, t + 3:])


def test_gated_conv_matches_the_reference_on_its_own():
    """``gated_conv`` against the reference's explicit sum over taps."""
    a = dict(reference_conv.TINY)
    d = a["hidden_size"]
    h = jax.random.normal(jax.random.key(0), (2, 16, d))
    p = {"l/norm1": 1.0 + 0.1 * jax.random.normal(jax.random.key(1), (d,)),
         "l/w_bcx": 0.2 * jax.random.normal(jax.random.key(2), (d, 3 * d)),
         "l/conv": jax.random.normal(jax.random.key(3), (3, d)),
         "l/wo": 0.2 * jax.random.normal(jax.random.key(4), (d, d))}
    layer = gated_conv.GatedShortConv(3, a["rms_norm_eps"])
    params = {"norm": p["l/norm1"], "bcx": p["l/w_bcx"], "conv": p["l/conv"], "o": p["l/wo"]}
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, h)
        want = reference_conv._conv_mixer(p, "l", h, a)
    np.testing.assert_allclose(got, want, atol=1e-5 * float(jnp.abs(want).max()))


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


# ------------------------------------------------- the attention layer


def test_attention_without_a_gate_is_an_einsum_head_by_head():
    """Query by query and head by head in float64: per-head q/k norms, the
    rotary turn over all of a head's dimensions (rotate-half), the grouped
    keys, and no gate: ``W_q`` holds the queries alone."""
    spec = TOKEN_ENCODERS[TINY]
    layer = gated_attention.GatedAttention(**token_encoder.gated_attrs(spec, jnp.float32),
                                           output_gate=False)
    h = jax.random.normal(jax.random.key(2), (3, 16, spec.hidden))
    params = layer.init(jax.random.key(5), h)["params"]
    H, G, d = spec.n_heads, spec.n_kv_heads, spec.head_dim
    assert params["q"].shape == (spec.hidden, H * d) and spec.rope_dim == d
    params = {name: (1.0 + 0.1 * jax.random.normal(jax.random.key(i), w.shape)
                     if "norm" in name else 8 * w) for i, (name, w) in enumerate(params.items())}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(layer.apply({"params": params}, h) - h, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True) + spec.rms_eps) * g  # noqa: E731

    def turn(x, t):
        out, half = x.copy(), d // 2
        for m in range(half):
            angle = t * spec.rope_theta ** (-m / half)
            out[m] = x[m] * np.cos(angle) - x[m + half] * np.sin(angle)
            out[m + half] = x[m + half] * np.cos(angle) + x[m] * np.sin(angle)
        return out

    want = np.zeros_like(got)
    for i, row in enumerate(np.asarray(h, np.float64)):
        a = rms(row, p["norm"])
        queries = (a @ p["q"]).reshape(16, H, d)
        keys = (a @ p["k"]).reshape(16, G, d)
        values = (a @ p["v"]).reshape(16, G, d)
        out = np.zeros((16, H, d))
        for n in range(H):
            g = n // (H // G)
            ks = np.stack([turn(rms(keys[s, g], p["k_norm"]), s) for s in range(16)])
            for t in range(16):
                query = turn(rms(queries[t, n], p["q_norm"]), t)
                logits = ks[: t + 1] @ query / np.sqrt(d)
                weights = np.exp(logits - logits.max())
                out[t, n] = (weights / weights.sum()) @ values[: t + 1, g]
        want[i] = out.reshape(16, H * d) @ p["o"]
    assert np.linalg.norm(want) > 1e-2
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def test_partial_rope_over_every_dimension_is_the_rotary_turn():
    cos, sin = (jnp.concatenate([x, x], -1)
                for x in gated_attention.rope_tables_1d(16, 8, 1e6))
    x = jax.random.normal(jax.random.key(0), (2, 16, 3, 8))
    np.testing.assert_array_equal(gated_attention.partial_rope(x, cos, sin),
                                  gated_attention.apply_rope(x, cos, sin))
    np.testing.assert_allclose(gated_attention.apply_rope(x[0], cos, sin),
                               reference_conv._turn(x[0], 1e6), atol=1e-5)


# -------------------------------------------------- the router and its bias


def test_the_gates_are_the_chosen_scores_over_their_sum_plus_eps():
    """``norm_topk_prob`` as published: ``s / (sum of the chosen s + 1e-6)``,
    times ``routed_scaling_factor`` 1; the bias chooses and never weighs."""
    logits = jax.random.normal(jax.random.key(0), (40, 8)) - 12.0  # small scores
    bias = 0.3 * jax.random.normal(jax.random.key(1), (8,))
    _, top_e, gates = experts.route(logits, 2, "sigmoid", bias, 1.0, 1e-6)
    scores = jax.nn.sigmoid(logits)
    np.testing.assert_array_equal(top_e, jax.lax.top_k(scores + bias, 2)[1])
    chosen = jnp.take_along_axis(scores, top_e, -1)
    np.testing.assert_allclose(gates, chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    # where the scores are of the order of the eps the two denominators differ
    _, _, plain = experts.route(logits, 2, "sigmoid", bias, 1.0)
    assert float(jnp.max(jnp.abs(plain - gates))) > 1e-3


def test_the_layer_moves_its_bias_towards_the_experts_under_a_balanced_load():
    """A train step moves each component by ``bias_rate`` against the sign of
    its expert's load above ``1 / E``; evaluation leaves it; no gradient
    reaches it."""
    spec = TOKEN_ENCODERS[TINY]
    layer = experts.ExpertLayer(**token_encoder.expert_attrs(spec, jnp.float32))
    h = jax.random.normal(jax.random.key(0), (2, 16, spec.hidden))
    variables = layer.init(jax.random.key(1), h)
    variables = {"params": dict(variables["params"], router=8 * variables["params"]["router"]),
                 "batch_stats": variables["batch_stats"]}
    (_, stats), moved = layer.apply(variables, h, True, mutable=["batch_stats"])
    bias = moved["batch_stats"]["route_bias"]
    load = stats["load"]
    np.testing.assert_allclose(bias, 0.001 * jnp.sign(1.0 / spec.n_experts - load), atol=1e-9)
    assert float(jnp.max(jnp.abs(bias))) == pytest.approx(0.001)
    _, still = layer.apply(variables, h, False, mutable=["batch_stats"])
    np.testing.assert_array_equal(still["batch_stats"]["route_bias"], 0.0)

    def loss(bias):
        out, _ = layer.apply({"params": variables["params"], "batch_stats": {"route_bias": bias}},
                             h, True, mutable=["batch_stats"])
        return jnp.sum(out[0])

    np.testing.assert_array_equal(jax.grad(loss)(jnp.zeros((spec.n_experts,))), 0.0)


# ---------------------------------------------------------- the expert share


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The published four-way split at test size: 8 experts, 2 held a share,
    2 a token, one bias for all. What every chip computes alike (the
    residual) counted once, the four shares' routed parts add up to the
    reference's layer with all 8 experts held; every assignment is held by
    exactly one share."""
    spec = TOKEN_ENCODERS[TINY]
    n_experts, shares, d = spec.n_experts, 4, spec.hidden
    a = dict(reference_conv.TINY, experts_held=[0, n_experts])
    attrs = dict(token_encoder.expert_attrs(spec, jnp.float32), held=(0, n_experts))
    h = jax.random.normal(jax.random.key(2), (3, 10, d))
    params = experts.ExpertLayer(**attrs).init(jax.random.key(5), h)["params"]
    params = dict(params, router=8 * params["router"],
                  **{n: 20 * params[n] for n in ("w_gate", "w_up", "w_down")})
    # a bias of the order of the scores' spread: it moves choices, and every
    # share still takes some
    bias = 0.03 * jax.random.normal(jax.random.key(6), (n_experts,))
    with jax.default_matmul_precision("highest"):
        uncut, _, _ = reference_conv._experts(
            {f"l/{k}": v for k, v in dict(params, norm2=params["norm"]).items()}, "l", h, bias, a)
        per, parts, held_shares = n_experts // shares, [], []
        for share in range(shares):
            cut = dict(params, **{n: params[n][per * share: per * (share + 1)]
                                  for n in ("w_gate", "w_up", "w_down")})
            layer = experts.ExpertLayer(**dict(attrs, held=(per * share, per)))
            out, part = jax.jit(layer.apply)({"params": cut, "batch_stats": {"route_bias": bias}},
                                             h)
            parts.append(out - h)  # this share's routed part alone
            held_shares.append(float(part["held_share"]))
    assert min(float(jnp.linalg.norm(part)) for part in parts) > 1e-2
    np.testing.assert_allclose(sum(parts), uncut - h, atol=2e-5)
    assert sum(held_shares) == pytest.approx(1.0)


def test_no_expert_layer_of_the_block_has_a_shared_expert():
    model = SupConResNet(model_name=TINY)
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, 16, 16, 3))))
    for k in range(1, 5):
        assert set(v["params"]["encoder"][f"block{k}"]["moe"]) == {
            "norm", "router", "w_gate", "w_up", "w_down"}


# ------------------------------------------------------------- the protocol


def test_both_new_names_are_known_everywhere(tmp_path):
    base = ["--dataset", "synthetic", "--workdir", str(tmp_path), "--batch_size", "4"]
    for name, size in ((TINY, 16), (REAL, 1024)):
        assert config_lib.parse_supcon(base + ["--model", name, "--size", str(size)]).model == name
        enc = build_encoder(name, dtype=jnp.bfloat16, remat=True, sync_bn=False)
        assert isinstance(enc, token_encoder.TokenEncoder) and enc.spec is TOKEN_ENCODERS[name]
        assert [enc.spec.attention_of(k) for k in range(5)] == KINDS
        assert token_encoder.attention_plan(name, size) == []  # no sparse-attention layer


@pytest.mark.parametrize("name", sorted(TOKEN_ENCODERS))
def test_match_tree_tells_every_preset_from_the_others(name):
    """The parameter tree names its preset, the new two among the five
    accepted ones and their tiny twins; the large ones unbuilt."""
    model = SupConResNet(model_name=name, head="linear", feat_dim=32)
    patch = TOKEN_ENCODERS[name].patch
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, patch, patch, 3))))
    assert token_encoder.match_tree(v["params"]["encoder"]) == name
    assert infer_architecture_from_variables(v) == (name, "linear", 32)


def test_tree_is_named_for_the_adapter():
    model = SupConResNet(model_name=TINY)
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, 16, 16, 3))))
    encoder = v["params"]["encoder"]
    assert set(encoder["block0"]["attn"]) == {"norm", "bcx", "conv", "o"}
    assert set(encoder["block0"]["mlp"]) == {"norm", "w_gate", "w_up", "w_down"}
    assert set(encoder["block1"]["attn"]) == {"norm", "q", "k", "v", "o", "q_norm", "k_norm"}
    stats = adapter_conv.to_reference(v["batch_stats"])
    assert sorted(stats) == sorted(reference_conv.running_names(TINY))
    assert reference_conv.stats_order(TINY) == [f"layer{i}/prob_mean" for i in range(1, 5)]
    spec = jax.eval_shape(lambda: reference_conv.init_params(jax.random.key(0), TINY, 128))
    assert jax.tree.structure(adapter_conv.to_program(spec, v["params"])) == jax.tree.structure(
        v["params"])


def test_program_and_reference_state_the_same_widths():
    """``TOKEN_ENCODERS`` against the configuration's file and the
    reference's tiny preset: one table each, no third."""
    keys = {"patch": "patch_size", "hidden": "hidden_size", "layers": "num_hidden_layers",
            "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
            "head_dim": "head_dim", "rope_theta": "rope_theta", "conv_width": "conv_L_cache",
            "dense_layers": "num_dense_layers", "dense_width": "intermediate_size",
            "n_experts": "num_experts", "top_k": "num_experts_per_tok",
            "expert_width": "moe_intermediate_size", "gate_scale": "routed_scaling_factor",
            "rms_eps": "rms_norm_eps", "bias_rate": "bias_update_rate"}
    for name in (TINY, REAL):
        spec, stated = TOKEN_ENCODERS[name], reference_conv.arch(name)
        assert {k: getattr(spec, k) for k in keys} == {k: stated[v] for k, v in keys.items()}
        assert list(spec.held) == stated["experts_held"]
        assert [{"full": "full_attention"}.get(k, k) for k in spec.layer_kinds] == (
            stated["layer_types"])
        assert (spec.router, spec.shared_width, spec.balance_coef, spec.gate_eps) == (
            "sigmoid", 0, 0.0, reference_conv.GATE_EPS)
        assert spec.rope_dim == spec.head_dim == spec.hidden // spec.n_heads  # full rotary
    real = reference_conv.arch(REAL)
    assert TOKEN_ENCODERS[REAL].capacity_factor == real["expert_capacity_factor"] == 2.0


def test_the_cut_keeps_the_published_widths_and_order():
    """The configuration's file: every number of the catalog's row under its
    own key, but the four that are cut, whose published values stand beside
    them; the layers kept are published layers 0 and 2-5."""
    import json

    with open(os.path.join(BENCH, "configs", f"{REAL}.json")) as f:
        stated = json.load(f)
    assert stated["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts",
                                 "vocab_size"]
    assert (stated["num_hidden_layers"], stated["num_dense_layers"], stated["num_experts"],
            stated["vocab_size"]) == (5, 1, 8, 0)
    assert stated["published"] == {"num_hidden_layers": 24, "num_dense_layers": 2,
                                   "num_experts": 32, "vocab_size": 65536}
    assert [stated["layer_types"][i] for i in stated["layers_kept"]] == (
        stated["architecture"]["layer_types"])
    assert (stated["hidden_size"], stated["intermediate_size"], stated["moe_intermediate_size"],
            stated["num_attention_heads"], stated["num_key_value_heads"],
            stated["num_experts_per_tok"], stated["conv_L_cache"], stated["rope_theta"],
            stated["norm_eps"], stated["routed_scaling_factor"]) == (
        2048, 7168, 1792, 32, 8, 4, 3, 1000000, 1e-5, 1)
    # 480.3 M parameters: 5.76 GB of weight, gradient and momentum
    model = SupConResNet(model_name=REAL)
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, 16, 16, 3))))
    count = sum(x.size for x in jax.tree.leaves(v["params"]))
    assert 480.2e6 < count < 480.4e6, count


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
    new_state, buffer = compiled(state, *args)
    return {"events": recorder.snapshot(), "ring": dict(zip(ring.keys, np.asarray(buffer)[0])),
            "text": compiled.as_text(), "state": new_state}


def test_build_says_what_the_layers_are(one_step):
    (plan,) = [r["args"] for r in one_step["events"] if r["name"] == "expert_plan"]
    assert (plan["layers"], plan["dense_layers"], plan["router"], plan["shared_width"],
            plan["shared_gate"], plan["held"], plan["n_experts"], plan["per_token"]) == (
        4, 1, "sigmoid", 0, False, 4, 8, 2)
    assert plan["ring_columns"] == list(TOKEN_ENCODERS[TINY].ring_columns)
    assert not [r for r in one_step["events"] if r["name"] in (
        "sparse_attention_plan", "latent_attention_plan", "linear_attention_plan")]


def test_step_writes_the_presets_columns_and_moves_the_bias(one_step):
    ring = one_step["ring"]
    assert 0.0 < ring["moe_held_share"] < 1.0 and np.isfinite(ring["loss"])
    assert ring["route_bias_max_abs"] == pytest.approx(0.001)
    encoder = one_step["state"].batch_stats["encoder"]
    for k in range(1, 5):
        assert float(jnp.max(jnp.abs(encoder[f"block{k}"]["moe"]["route_bias"]))) == (
            pytest.approx(0.001))


@pytest.mark.parametrize("scope", [
    r"encoder/block0/attn/conv_mixer/", r"encoder/block2/attn/conv_mixer/[^\"]*gated_conv/",
    r"transpose\(jvp\(SupConResNet\)\)/encoder/block4/attn/conv_mixer/[^\"]*gated_conv/",
    r"encoder/block1/attn/[^\"]*attn_core/", r"encoder/block0/mlp/",
    r"encoder/block1/moe/[^\"]*experts/"])
def test_step_names_the_new_scopes(one_step, scope):
    import re

    assert re.search(scope, one_step["text"]), scope
