"""The token encoder's second block (Moonlight-16B-A3B's: latent attention
with one shared rotary key, a leading dense layer, sigmoid-routed experts
under a load-correcting bias beside shared experts) against its plain
reference (benchmark/reference_latent.py), at the tiny preset on the CPU with
seeded weights; the latent layer, the rotary key, the bias and the expert
share on their own; and the first block's presets, which must stay the
programs they were.

``tests/token_encoder_golden.json`` holds, for Keye's two presets, digests of
the parameter tree, of the lowered forward-and-backward (StableHLO text, no
locations) and of its ``op_name``s at commit ``60c8e05`` (PR 31), which the
compile cache keys on. It was written by this file, run against that commit:

    PYTHONPATH=<checkout of 60c8e05> python tests/test_latent_encoder.py tests/token_encoder_golden.json

A PR that means to move the Keye cell's program writes it anew and says so.
"""

import collections
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from simclr_pytorch_distributed_tpu.models import SupConResNet  # noqa: E402

TINY = "moonlight-tiny"
REAL = "moonlight-16b-a3b-ep8"
GOLDEN = os.path.join(HERE, "token_encoder_golden.json")
KEYE = (("keye-vl2-tiny", 16, 4), ("keye-vl2-a3b-ep8", 1024, 8))  # preset, view side, rows


def digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def fingerprint(name: str, size: int, rows: int) -> dict:
    """Digests of ``name``'s parameter tree and of its lowered train-mode
    forward and backward with ``--remat`` (nothing is compiled or run)."""
    model = SupConResNet(model_name=name, remat=True)
    x = jnp.zeros((rows, size, size, 3))
    v = jax.eval_shape(lambda: model.init(jax.random.key(1), x, train=True))

    def loss(p, stats, x):
        feats, mutated = model.apply({"params": p, "batch_stats": stats}, x, train=True,
                                     mutable=["batch_stats", "aux"])
        aux_loss, metrics = model.read_aux(mutated["aux"])
        return jnp.sum(feats) + aux_loss, (mutated["batch_stats"], metrics)

    lowered = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        v["params"], v["batch_stats"], x)
    names = collections.Counter(
        n for n in re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
        if n.startswith("jit("))
    leaves = jax.tree_util.tree_flatten_with_path(v)[0]
    tree = sorted(("/".join(k.key for k in path), list(leaf.shape)) for path, leaf in leaves)
    return {"tree": digest(json.dumps(tree)), "leaves": len(tree),
            "stablehlo": digest(lowered.as_text()),
            "op_names": digest(json.dumps(sorted(names.items()))), "n_op_names": sum(names.values())}


if __name__ == "__main__":  # see the module docstring
    with open(sys.argv[1], "w") as f:
        json.dump({name: fingerprint(name, size, rows) for name, size, rows in KEYE}, f, indent=1)
    sys.exit(0)

import adapter_latent  # noqa: E402
import reference_latent  # noqa: E402

from simclr_pytorch_distributed_tpu import config as config_lib  # noqa: E402
from simclr_pytorch_distributed_tpu import recipes as recipes_lib  # noqa: E402
from simclr_pytorch_distributed_tpu.models import (  # noqa: E402
    TOKEN_ENCODERS,
    build_encoder,
    experts,
    infer_architecture_from_variables,
    latent_attention,
    token_encoder,
)


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ------------------------------------------------ program against reference


@pytest.fixture(scope="module")
def both_sides():
    """Loss, gradients and the statistics after one train-mode forward, and
    the bias after two, of the program and of the reference, on weights
    moved off their initial symmetry (norms not 1, matrices three times
    their deviation so that the router chooses firmly)."""
    with jax.default_matmul_precision("highest"):
        model = SupConResNet(model_name=TINY, remat=True)
        views = jax.random.normal(jax.random.key(4), (6, 16, 16, 3))
        shape = jax.eval_shape(lambda: model.init(jax.random.key(0), views, train=True))
        key = jax.random.key(3)
        ref_params = {
            name: (w + 0.1 * jax.random.normal(jax.random.fold_in(key, 99 + i), w.shape)
                   if "norm" in name else 3 * w)
            for i, (name, w) in enumerate(sorted(
                reference_latent.init_params(key, TINY, 128).items()))}
        params = adapter_latent.to_program(ref_params, shape["params"])
        stats0 = jax.tree.map(jnp.zeros_like, shape["batch_stats"])

        def program(p, stats):
            feats, mutated = model.apply({"params": p, "batch_stats": stats}, views,
                                         train=True, mutable=["batch_stats", "aux"])
            aux_loss, metrics = model.read_aux(mutated["aux"])
            return jnp.sum(jnp.sin(feats)) + aux_loss, (mutated["batch_stats"], metrics)

        def reference(p, running):
            feats, aux_loss, stats = reference_latent.forward(p, views, TINY, running)
            return jnp.sum(jnp.sin(feats)) + aux_loss, stats

        (loss_p, (stats_p, metrics)), grads_p = jax.value_and_grad(program, has_aux=True)(
            params, stats0)
        running0 = reference_latent.running_at_rest(ref_params)
        (loss_r, stats_r), grads_r = jax.value_and_grad(reference, has_aux=True)(
            ref_params, running0)
        running1 = reference_latent.step_running(running0, stats_r, TINY, 0.1)
        # a second step on the same weights: the first step's bias now chooses
        _, (stats_p2, _) = program(params, stats_p)
        _, stats_r2 = reference(ref_params, running1)
        running2 = reference_latent.step_running(running1, stats_r2, TINY, 0.1)
    return {"loss": (float(loss_p), float(loss_r)),
            "grads": (adapter_latent.to_reference(grads_p), grads_r),
            "stats": (adapter_latent.to_reference(stats_p), running1),
            "stats2": (adapter_latent.to_reference(stats_p2), running2), "metrics": metrics}


def test_loss_agrees_with_the_reference(both_sides):
    program, reference = both_sides["loss"]
    assert abs(program - reference) <= 1e-5 * abs(reference)


@pytest.mark.parametrize("name", sorted(reference_latent.param_spec(TINY)))
def test_gradient_leaf_agrees_with_the_reference(both_sides, name):
    program, reference = both_sides["grads"]
    assert float(jnp.linalg.norm(reference[name])) > 0
    assert rel(program[name], reference[name]) <= 1e-4


@pytest.mark.parametrize("name", reference_latent.running_names(TINY))
def test_running_statistic_agrees_with_the_reference(both_sides, name):
    """One step from rest: a tenth of the batch's means, and the bias one
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
    for name in reference_latent.running_names(TINY):
        np.testing.assert_allclose(program[name], reference[name], atol=1e-7)
    bias = program["layer1/route_bias"]
    assert float(jnp.max(jnp.abs(bias))) == pytest.approx(0.002)  # an expert pushed twice


def test_ring_columns_read_the_routing(both_sides):
    m = both_sides["metrics"]
    assert tuple(m) == TOKEN_ENCODERS[TINY].ring_columns == (
        "moe_held_share", "moe_load_max_over_mean", "route_bias_max_abs")
    assert 0.0 < float(m["moe_held_share"]) < 1.0  # half of the experts held
    assert float(m["moe_load_max_over_mean"]) >= 1.0
    assert float(m["route_bias_max_abs"]) == pytest.approx(0.001)


# ------------------------------------------------------- the latent layer


@pytest.fixture(scope="module")
def latent_layer():
    spec = TOKEN_ENCODERS[TINY]
    layer = latent_attention.LatentAttention(**token_encoder.latent_attrs(spec, jnp.float32))
    h = jax.random.normal(jax.random.key(2), (3, 16, spec.hidden))
    params = layer.init(jax.random.key(5), h)["params"]
    params = {name: (1.0 + 0.1 * jax.random.normal(jax.random.key(i), w.shape)
                     if "norm" in name else 8 * w) for i, (name, w) in enumerate(params.items())}
    return spec, layer, params, h


def rotate(x, position, theta):
    """One vector's pairs ``(2m, 2m + 1)`` turned by ``position * theta **
    (-2m / d)``, written out pair by pair."""
    out = np.array(x, np.float64)
    d = len(out)
    for m in range(d // 2):
        angle = position * theta ** (-2 * m / d)
        a, b = out[2 * m], out[2 * m + 1]
        out[2 * m] = a * np.cos(angle) - b * np.sin(angle)
        out[2 * m + 1] = b * np.cos(angle) + a * np.sin(angle)
    return out


def test_latent_layer_is_attention_head_by_head_over_explicit_keys(latent_layer):
    """Every head's keys built explicitly (its own ``k_n`` beside the one
    rotary key), query by query and head by head in float64."""
    spec, layer, params, h = latent_layer
    with jax.default_matmul_precision("highest"):
        got = np.asarray(layer.apply({"params": params}, h) - h, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    H, r, dn, dr, dv = spec.n_heads, spec.kv_rank, spec.nope_dim, spec.rope_dim, spec.v_dim
    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True) + spec.rms_eps) * g  # noqa: E731
    want = np.zeros_like(got)
    for i, row in enumerate(np.asarray(h, np.float64)):
        a = rms(row, p["norm"])
        q = (a @ p["q"]).reshape(16, H, dn + dr)
        c = a @ p["kv_a"]
        kv = (rms(c[:, :r], p["kv_norm"]) @ p["kv_b"]).reshape(16, H, dn + dv)
        k_rope = np.stack([rotate(c[t, r:], t, spec.rope_theta) for t in range(16)])
        out = np.zeros((16, H, dv))
        for n in range(H):
            keys = np.concatenate([kv[:, n, :dn], k_rope], axis=-1)  # [T, dn + dr]
            for t in range(16):
                query = np.concatenate([q[t, n, :dn], rotate(q[t, n, dn:], t, spec.rope_theta)])
                logits = keys[: t + 1] @ query / np.sqrt(dn + dr)
                weights = np.exp(logits - logits.max())
                out[t, n] = (weights / weights.sum()) @ kv[: t + 1, n, dn:]
        want[i] = out.reshape(16, H * dv) @ p["o"]
    assert np.linalg.norm(want) > 1e-2
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def test_rotary_key_is_one_head_and_turns_by_the_raster_index(latent_layer):
    spec, layer, params, h = latent_layer
    assert params["kv_a"].shape == (spec.hidden, spec.kv_rank + spec.rope_dim)  # one head's
    cos, sin = latent_attention.rope_tables_1d(16, spec.rope_dim, spec.rope_theta)
    x = jax.random.normal(jax.random.key(1), (16, 3, spec.rope_dim))
    turned = latent_attention.apply_rope_pairs(x, cos, sin)
    np.testing.assert_allclose(turned[0], x[0], atol=1e-6)  # token 0 does not turn
    for t in (1, 5, 15):  # raster index, not row and column: token 5 is (1, 1) of the 4x4 grid
        np.testing.assert_allclose(turned[t, 2], rotate(x[t, 2], t, spec.rope_theta), atol=1e-5)
    np.testing.assert_allclose(turned, reference_latent._rotary(x, spec.rope_theta), atol=1e-6)
    # a pair's length is kept, and relative position is all a score sees
    np.testing.assert_allclose(jnp.linalg.norm(turned, axis=-1), jnp.linalg.norm(x, axis=-1),
                               rtol=1e-5)
    same = jnp.broadcast_to(x[:1], x.shape)
    t = latent_attention.apply_rope_pairs(same, cos, sin)
    np.testing.assert_allclose(jnp.sum(t[7, 0] * t[4, 0]), jnp.sum(t[10, 0] * t[7, 0]), rtol=1e-4)


# -------------------------------------------------- the router and its bias


def test_the_bias_chooses_and_does_not_weigh():
    logits = jax.random.normal(jax.random.key(0), (40, 8))
    zero = jnp.zeros((8,))
    probs, top_e, gates = experts.route(logits, 2, "sigmoid", zero, 2.446)
    scores = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.446, rtol=1e-6)  # normalised, then scaled
    np.testing.assert_allclose(probs, scores / jnp.sum(scores, -1, keepdims=True), rtol=1e-6)
    np.testing.assert_array_equal(top_e, jax.lax.top_k(scores, 2)[1])
    # a large bias on expert 5: every token now chooses it ...
    pushed, top_b, gates_b = experts.route(logits, 2, "sigmoid", zero.at[5].set(10.0), 2.446)
    np.testing.assert_allclose(pushed, probs)
    assert bool(jnp.all(jnp.any(top_b == 5, axis=-1))) and not bool(jnp.all(jnp.any(top_e == 5, -1)))
    # ... its gate is still its unbiased score's share, and the other chosen
    # expert is the one of largest unbiased score with its share of the pair
    other = jnp.where(top_b[:, 0] == 5, top_b[:, 1], top_b[:, 0])
    best_other = jnp.argmax(scores.at[:, 5].set(-1.0), axis=-1)
    np.testing.assert_array_equal(other, best_other)
    s5, so = scores[:, 5], jnp.take_along_axis(scores, other[:, None], -1)[:, 0]
    gate_of = lambda e: jnp.sum(jnp.where(top_b == e[:, None], gates_b, 0.0), -1)  # noqa: E731
    np.testing.assert_allclose(gate_of(jnp.full((40,), 5)), 2.446 * s5 / (s5 + so), rtol=1e-5)
    np.testing.assert_allclose(gate_of(other), 2.446 * so / (s5 + so), rtol=1e-5)


def test_softmax_rule_is_what_it_was():
    logits = jax.random.normal(jax.random.key(0), (40, 8))
    probs, top_e, gates = experts.route(logits, 2)
    np.testing.assert_allclose(probs, jax.nn.softmax(logits, -1), rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(gates, -1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(top_e, jax.lax.top_k(logits, 2)[1])


def test_sequence_balance_is_the_balance_term_row_by_row():
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(1), (3 * 10, 8)), -1)
    _, top_e = jax.lax.top_k(probs, 2)
    rows = [8 * jnp.sum(load * prob) for load, prob in (
        experts.routing_statistics(probs[10 * r: 10 * r + 10], top_e[10 * r: 10 * r + 10])
        for r in range(3))]
    assert float(experts.sequence_balance(probs, top_e, 3)) == pytest.approx(
        float(sum(rows) / 3), rel=1e-6)


# ---------------------------------------------------------- the expert share


@pytest.mark.parametrize("shares", [2, 8])
def test_the_shares_add_up_with_the_shared_experts_counted_once(shares):
    """The held experts' parts that ``shares`` chips give, with what every
    chip computes alike (the shared experts) counted once, against the uncut
    layer: the reference with all experts held."""
    n_experts, per_token, width, shared, d = 16, 4, 8, 12, 12
    a = dict(reference_latent.TINY, num_experts=n_experts, num_experts_per_tok=per_token,
             moe_intermediate_size=width, hidden_size=d, experts_held=[0, n_experts])
    attrs = dict(n_experts=n_experts, top_k=per_token, width=width, router="sigmoid",
                 gate_scale=a["routed_scaling_factor"], shared_width=shared, rms_eps=1e-5)
    h = jax.random.normal(jax.random.key(2), (3, 10, d))
    params = experts.ExpertLayer(held=(0, n_experts), **attrs).init(jax.random.key(5), h)["params"]
    params = dict(params, router=8 * params["router"], **{
        n: 20 * params[n] for n in params if n.startswith(("w_", "shared_"))})
    bias = 0.3 * jax.random.normal(jax.random.key(6), (n_experts,))
    stats = {"route_bias": bias}
    with jax.default_matmul_precision("highest"):
        uncut, *_ = reference_latent._experts(
            {f"l/{k}": v for k, v in dict(params, norm2=params["norm"]).items()}, "l", h, bias, a)
        only_shared = experts.gated_mlp(
            experts.rms_norm(h, params["norm"], 1e-5),
            *(params[n] for n in ("shared_gate", "shared_up", "shared_down")))
        per, parts, held_shares = n_experts // shares, [], []
        for share in range(shares):
            cut = dict(params, **{n: params[n][per * share: per * (share + 1)]
                                  for n in ("w_gate", "w_up", "w_down")})
            out, part = experts.ExpertLayer(held=(per * share, per), **attrs).apply(
                {"params": cut, "batch_stats": stats}, h)
            parts.append(out - h - only_shared)  # the held experts' part alone
            held_shares.append(float(part["held_share"]))
    assert float(jnp.linalg.norm(only_shared)) > 1e-2 < float(jnp.linalg.norm(sum(parts)))
    np.testing.assert_allclose(sum(parts) + only_shared, uncut - h, atol=2e-5)
    assert sum(held_shares) == pytest.approx(1.0)


# ------------------------------------------------ the first block's presets


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("name,size,rows", KEYE)
def test_keye_presets_are_the_programs_they_were(golden, name, size, rows):
    """Parameter tree, lowered computation and ``op_name``s of PR 31's
    commit: the accepted cell's program must not move (the compile cache
    keys on the ``op_name``s too)."""
    assert fingerprint(name, size, rows) == golden[name]


def test_keye_presets_name_the_scopes_they_did():
    model = SupConResNet(model_name="keye-vl2-tiny")
    x = jnp.zeros((2, 16, 16, 3))
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), x, train=True))
    text = jax.jit(lambda v, x: model.apply(v, x, train=True, mutable=["batch_stats", "aux"])).lower(
        v, x).as_text(debug_info=True)
    for scope in ("SupConResNet/encoder/block0/attn/", "SupConResNet/encoder/block1/moe/",
                  "indexer/", "experts/", "SupConResNet/encoder/patch_embed"):
        assert scope in text, scope
    for scope in ("/latent/", "/attn_core/", "/shared/", "/mlp/"):
        assert scope not in text, scope
    assert set(v["batch_stats"]["encoder"]["block0"]) == {"prob_mean", "load_mean"}
    assert model.aux_metric_keys == token_encoder.AUX_METRIC_KEYS


# ------------------------------------------------------------- the protocol


def test_both_new_names_are_known_everywhere(tmp_path):
    base = ["--dataset", "synthetic", "--workdir", str(tmp_path), "--batch_size", "4"]
    for name, size in ((TINY, 16), (REAL, 1024)):
        assert config_lib.parse_supcon(base + ["--model", name, "--size", str(size)]).model == name
        enc = build_encoder(name, dtype=jnp.bfloat16, remat=True, sync_bn=False)
        assert isinstance(enc, token_encoder.TokenEncoder) and enc.spec is TOKEN_ENCODERS[name]
        assert enc.aux_metric_keys == ("moe_held_share", "moe_load_max_over_mean",
                                       "route_bias_max_abs")
        assert token_encoder.attention_plan(name, size) == []  # no sparse-attention layer
    with pytest.raises(ValueError, match="patches"):
        config_lib.parse_supcon(base + ["--model", TINY, "--size", "18"])
    for name in TOKEN_ENCODERS:  # every preset's tree names its preset, the large ones unbuilt
        model = SupConResNet(model_name=name, head="linear", feat_dim=32)
        patch = TOKEN_ENCODERS[name].patch
        v = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, patch, patch, 3))))
        assert infer_architecture_from_variables(v) == (name, "linear", 32)
    assert token_encoder.match_tree({"conv1": {}}) is None


def test_tree_is_named_for_the_adapter():
    model = SupConResNet(model_name=TINY)
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, 16, 16, 3))))
    assert set(v["params"]["encoder"]["block0"]) == {"attn", "mlp"}
    assert set(v["params"]["encoder"]["block1"]) == {"attn", "moe"}
    stats = adapter_latent.to_reference(v["batch_stats"])
    assert sorted(stats) == sorted(reference_latent.running_names(TINY))
    # of them the harness compares the smooth one (reference_latent.stats_order)
    assert reference_latent.stats_order(TINY) == ["layer1/prob_mean"] == list(
        reference_latent.init_running({"layer1/router": jnp.zeros((32, 8))}))
    spec = jax.eval_shape(lambda: reference_latent.init_params(jax.random.key(0), TINY, 128))
    assert jax.tree.structure(adapter_latent.to_program(spec, v["params"])) == jax.tree.structure(
        v["params"])


def test_program_and_reference_state_the_same_widths():
    """``TOKEN_ENCODERS`` against the configuration's file and the
    reference's tiny preset: one table each, no third."""
    keys = {"patch": "patch_size", "hidden": "hidden_size", "layers": "num_hidden_layers",
            "dense_layers": "first_k_dense_replace", "dense_width": "intermediate_size",
            "n_heads": "num_attention_heads", "kv_rank": "kv_lora_rank",
            "nope_dim": "qk_nope_head_dim", "rope_dim": "qk_rope_head_dim",
            "v_dim": "v_head_dim", "rope_theta": "rope_theta", "n_experts": "num_experts",
            "top_k": "num_experts_per_tok", "expert_width": "moe_intermediate_size",
            "shared_width": "shared_intermediate_size", "gate_scale": "routed_scaling_factor",
            "rms_eps": "rms_norm_eps", "bias_rate": "bias_update_rate",
            "balance_coef": "balance_coef"}
    for name in (TINY, REAL):
        spec, stated = TOKEN_ENCODERS[name], reference_latent.arch(name)
        assert {k: getattr(spec, k) for k in keys} == {k: stated[v] for k, v in keys.items()}
        assert list(spec.held) == stated["experts_held"]
        assert stated["shared_intermediate_size"] == (
            stated["n_shared_experts"] * stated["moe_intermediate_size"] if name == REAL else 24)
        assert (spec.attention, spec.router, spec.sequence_balance) == ("latent", "sigmoid", True)
    real = reference_latent.arch(REAL)
    assert TOKEN_ENCODERS[REAL].capacity_factor == real["expert_capacity_factor"] == 2.0
    # the published widths, unchanged
    assert (real["hidden_size"], real["num_attention_heads"], real["qk_nope_head_dim"],
            real["qk_rope_head_dim"], real["v_head_dim"], real["kv_lora_rank"],
            real["intermediate_size"], real["moe_intermediate_size"], real["num_experts"],
            real["num_experts_per_tok"], real["routed_scaling_factor"]) == (
        2048, 16, 128, 64, 128, 512, 11264, 1408, 64, 6, 2.446)


def test_the_cells_trip_is_what_the_budget_gives():
    """The benchmark's step at the real preset: 8 rows of 4,096 tokens, 6
    experts a token, 8 of 64 held, two balanced shares provisioned."""
    spec, assignments = TOKEN_ENCODERS[REAL], 8 * 4096 * 6
    provisioned = experts.provisioned_rows(assignments, 8, 64, spec.capacity_factor)
    assert (assignments, provisioned) == (196608, 49152)
    assert experts.balanced_chunk_rows(assignments, 8, 64, provisioned, spec.hidden,
                                       spec.expert_width, jnp.float32) == 24576


# ------------------------------------------------------------ the train step


@pytest.fixture(scope="module")
def one_step(tmp_path_factory):
    """Two updates through ``train.supcon.build`` and ``make_fused_update``."""
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
    text = update.lower(state, *args).compile().as_text()  # op_names, whole paths
    before = jax.tree.map(np.asarray, state.batch_stats)
    new_state, buffer = update(state, *args)
    return {"events": recorder.snapshot(), "ring": dict(zip(ring.keys, np.asarray(buffer)[0])),
            "before": before, "state": new_state, "text": text}


def test_build_says_what_the_layers_are(one_step):
    plans = [r for r in one_step["events"] if r["name"] == "expert_plan"]
    assert len(plans) == 1 and plans[0]["track"] == "compile"
    assert plans[0]["args"] == {"layers": 1, "held": 4, "first": 0, "n_experts": 8,
                                "per_token": 2, "rows_per_step": 8 * 16,
                                "capacity_factor": 2.0, "provisioned_assignments": 8 * 16 * 2,
                                "rows_per_trip": 8 * 16 * 2, "provisioned_trips": 1,
                                "dense_layers": 1, "router": "sigmoid", "shared_width": 24,
                                "shared_gate": False, "product_operands": "float32",
                                "product_reason": "non-TPU backend (cpu)",
                                "ring_columns": list(TOKEN_ENCODERS[TINY].ring_columns)}
    latent = [r for r in one_step["events"] if r["name"] == "latent_attention_plan"]
    assert len(latent) == 1 and latent[0]["track"] == "compile"
    assert {k: latent[0]["args"][k] for k in ("layers", "heads", "nope_dim", "rope_dim", "v_dim",
                                               "kv_rank", "tokens", "path")} == {
        "layers": 2, "heads": 4, "nope_dim": 8, "rope_dim": 4, "v_dim": 8, "kv_rank": 16,
        "tokens": 16, "path": "xla"}
    assert "value heads of 8" in latent[0]["args"]["reason"]
    assert not [r for r in one_step["events"] if r["name"] == "sparse_attention_plan"]


@pytest.mark.parametrize("backend,flags,operands,reason", [
    ("cpu", [], "float32", "non-TPU backend (cpu)"),
    ("tpu", [], "bfloat16", None),
    ("tpu", ["--bf16"], "bfloat16", "--bf16"),
    ("cpu", ["--bf16"], "bfloat16", "--bf16")])
@pytest.mark.parametrize("name", [TINY, "keye-vl2-tiny"])
def test_build_gives_the_grouped_products_what_default_precision_would_round_to(
        monkeypatch, tmp_path, name, backend, flags, operands, reason):
    """bfloat16 operands exactly where the layers are float32 and the program
    is a TPU's (there default precision rounds the same operands inside every
    call); off the TPU a float32 product is exact and stays, and under
    ``--bf16`` the operands are bfloat16 already. The model, the banner and
    the ``expert_plan`` event say the same."""
    from simclr_pytorch_distributed_tpu.train import supcon
    from simclr_pytorch_distributed_tpu.utils import tracing

    monkeypatch.setattr(supcon.jax, "default_backend", lambda: backend)
    cfg = config_lib.parse_supcon([
        "--dataset", "synthetic", "--workdir", str(tmp_path), "--batch_size", "4", "--size", "16",
        "--model", name, "--loss_impl", "dense", "--health_freq", "0", "--ngpu", "auto", *flags])
    recorder, built = tracing.FlightRecorder(), []
    tracing.install(recorder)
    try:  # traced, nothing run; four devices: their number does not stand it down
        jax.eval_shape(lambda: built.extend(supcon.build(cfg, 5, 4)))
    finally:
        tracing.uninstall()
    (plan,) = [r["args"] for r in recorder.snapshot() if r["name"] == "expert_plan"]
    assert (plan["product_operands"], plan["product_reason"]) == (operands, reason)
    assert jnp.dtype(built[0].build_encoder().expert_product_dtype).name == operands
    assert plan["rows_per_trip"] == 8 * 16 * 2 and plan["provisioned_trips"] == 1


def test_step_writes_the_presets_columns_and_moves_the_statistics(one_step):
    ring = one_step["ring"]
    assert set(TOKEN_ENCODERS[TINY].ring_columns) <= set(ring) and "indexer_kl" not in ring
    assert 0.0 < ring["moe_held_share"] < 1.0 and ring["moe_load_max_over_mean"] >= 1.0
    assert ring["route_bias_max_abs"] == pytest.approx(0.001) and np.isfinite(ring["loss"])
    encoder = one_step["state"].batch_stats["encoder"]
    assert set(encoder) == {"block1"}  # the dense layer keeps none
    assert float(jnp.sum(encoder["block1"]["load_mean"])) == pytest.approx(0.1, rel=1e-5)
    assert float(jnp.sum(encoder["block1"]["prob_mean"])) == pytest.approx(0.1, rel=1e-5)
    assert float(jnp.max(jnp.abs(encoder["block1"]["moe"]["route_bias"]))) == pytest.approx(0.001)
    assert all(not np.any(leaf) for leaf in jax.tree.leaves(one_step["before"]))  # from rest


@pytest.mark.parametrize("scope", [
    r"encoder/block0/attn/", r"encoder/block1/attn/[^\"]*latent/",
    r"encoder/block0/attn/[^\"]*attn_core/", r"encoder/block0/mlp/", r"encoder/block1/moe/",
    r"encoder/block1/moe/[^\"]*shared/", r"encoder/block1/moe/[^\"]*experts/",
    r"transpose\(jvp\(SupConResNet\)\)/encoder/block0/mlp/"])
def test_step_names_the_new_scopes(one_step, scope):
    assert re.search(scope, one_step["text"]), scope


def test_trace_report_prints_both_plans():
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "scripts"))
    import trace_report

    span = {"name": "flush_boundary", "track": "main:flush", "ph": "X", "ts": 0.0, "dur": 1.0,
            "args": {}}
    plan = {"layers": 4, "held": 8, "first": 0, "n_experts": 64, "per_token": 6,
            "rows_per_step": 32768, "capacity_factor": 2.0, "provisioned_assignments": 49152,
            "rows_per_trip": 24576, "provisioned_trips": 2, "dense_layers": 1,
            "router": "sigmoid", "shared_width": 2816, "product_operands": "float32",
            "product_reason": "non-TPU backend (cpu)",
            "ring_columns": ["moe_held_share", "route_bias_max_abs"]}
    latent = {"layers": 5, "heads": 16, "nope_dim": 128, "rope_dim": 64, "v_dim": 128,
              "kv_rank": 512, "tokens": 4096, "path": "xla", "reason": "no kernel"}
    events = [span,
              {"name": "expert_plan", "track": "compile", "ph": "i", "ts": 0.1, "args": plan},
              {"name": "latent_attention_plan", "track": "compile", "ph": "i", "ts": 0.1,
               "args": latent},
              {"name": "health_window", "track": "health", "ph": "i", "ts": 0.5,
               "args": {"moe_held_share": 0.124, "route_bias_max_abs": 0.012, "step": 10}}]
    report = trace_report.build_report(events)
    assert report["encoder"] == {"expert_plan": plan, "latent_attention_plan": latent, "ring": {
        "moe_held_share": 0.124, "route_bias_max_abs": 0.012}}
    table = trace_report.render_table(report)
    assert ("experts: 4 layers hold 8 of 64, 6 a token (sigmoid-routed, after 1 dense layers, "
            "shared experts of width 2816)") in table
    assert ("in 2 trips of 24576 rows, grouped products on float32 operands (non-TPU backend "
            "(cpu)); moe_held_share") in table
    assert "route_bias_max_abs 0.012" in table
    assert ("latent attention: 5 layers of 16 heads (128 + 64 shared rotary / 128), latent of "
            "512, 4096 tokens a row, on xla's path: no kernel") in table
