"""The token encoder's first block (models/token_encoder.py: sparse attention
behind a learned key indexer, routed experts of which this chip holds a
share; tests/test_latent_encoder.py has the second block) against
its plain reference (benchmark/reference_tokens.py), at the tiny preset on
the CPU with seeded weights; the selection and the expert share on their own;
and the ResNets through the encoder protocol that the token encoder brought.
"""

import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import adapter_tokens  # noqa: E402
import reference_tokens  # noqa: E402

from simclr_pytorch_distributed_tpu import config as config_lib  # noqa: E402
from simclr_pytorch_distributed_tpu import recipes as recipes_lib  # noqa: E402
from simclr_pytorch_distributed_tpu.models import (  # noqa: E402
    MODEL_DICT,
    TOKEN_ENCODERS,
    SupConResNet,
    build_encoder,
    experts,
    infer_architecture_from_variables,
    resnet,
    sparse_attention,
    token_encoder,
)

TINY = "keye-vl2-tiny"
REAL = "keye-vl2-a3b-ep8"


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ------------------------------------------------ program against reference


@pytest.fixture(scope="module")
def both_sides():
    """Loss, gradients and the statistics' step of the program and of the
    reference, on weights moved off their initial symmetry (norms not 1, the
    indexer's and the router's matrices large enough to choose firmly)."""
    with jax.default_matmul_precision("highest"):
        model = SupConResNet(model_name=TINY, remat=True)
        views = jax.random.normal(jax.random.key(4), (6, 16, 16, 3))
        shape = jax.eval_shape(lambda: model.init(jax.random.key(0), views, train=True))
        key = jax.random.key(3)
        ref_params = {
            name: (w + 0.1 * jax.random.normal(jax.random.fold_in(key, 99 + i), w.shape)
                   if "norm" in name else w * (8 if "index" in name else 3))
            for i, (name, w) in enumerate(sorted(
                reference_tokens.init_params(key, TINY, 128).items()))}
        params = adapter_tokens.to_program(ref_params, shape["params"])
        stats0 = jax.tree.map(jnp.zeros_like, shape["batch_stats"])

        def program(p):
            feats, mutated = model.apply({"params": p, "batch_stats": stats0}, views,
                                         train=True, mutable=["batch_stats", "aux"])
            aux_loss, metrics = model.read_aux(mutated["aux"])
            return jnp.sum(jnp.sin(feats)) + aux_loss, (mutated["batch_stats"], metrics)

        def reference(p):
            feats, aux_loss, stats = reference_tokens.forward(p, views, TINY)
            return jnp.sum(jnp.sin(feats)) + aux_loss, stats

        (loss_p, (stats_p, metrics)), grads_p = jax.value_and_grad(program, has_aux=True)(params)
        (loss_r, stats_r), grads_r = jax.value_and_grad(reference, has_aux=True)(ref_params)
    return {"loss": (float(loss_p), float(loss_r)),
            "grads": (adapter_tokens.to_reference(grads_p), grads_r),
            "stats": (adapter_tokens.to_reference(stats_p), stats_r), "metrics": metrics}


def test_loss_agrees_with_the_reference(both_sides):
    program, reference = both_sides["loss"]
    assert abs(program - reference) <= 1e-5 * abs(reference)


@pytest.mark.parametrize("name", sorted(reference_tokens.param_spec(TINY)))
def test_gradient_leaf_agrees_with_the_reference(both_sides, name):
    program, reference = both_sides["grads"]
    assert float(jnp.linalg.norm(reference[name])) > 0
    assert rel(program[name], reference[name]) <= 1e-4


@pytest.mark.parametrize("name", reference_tokens.stats_order(TINY))
def test_running_statistic_agrees_with_the_reference(both_sides, name):
    """One step from rest with momentum 0.1: a tenth of the batch's statistic."""
    program, reference = both_sides["stats"]
    np.testing.assert_allclose(program[name], 0.1 * reference[name], atol=1e-7)
    assert float(jnp.sum(reference[name])) == pytest.approx(1.0, abs=1e-5)  # shares of a whole


def test_ring_columns_read_the_routing(both_sides):
    m = both_sides["metrics"]
    assert set(m) == set(token_encoder.AUX_METRIC_KEYS)
    assert 0.0 < float(m["moe_held_share"]) < 1.0  # half of the experts held
    assert float(m["moe_load_max_over_mean"]) >= 1.0 and float(m["indexer_kl"]) > 0.0


# ------------------------------------------------------------ the selection


@pytest.mark.parametrize("k", [1, 3, 6, 16])
def test_select_topk_is_lax_top_k_as_a_mask(k):
    """Scores with many exact ties (a few distinct values), a causal mask
    over them: the mask is what ``lax.top_k`` picks, ties to the lower index."""
    n = 16
    scores = jnp.round(2 * jax.random.normal(jax.random.key(k), (3, 12, n))) / 2
    valid = jnp.arange(n)[None, :] <= jnp.arange(4, 16)[:, None]
    got = sparse_attention.select_topk(scores, valid, k)
    _, picked = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
    want = jnp.zeros(scores.shape, bool).at[
        jnp.arange(3)[:, None, None], jnp.arange(12)[None, :, None], picked].set(True) & valid
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(jnp.max(jnp.sum(got, axis=-1))) <= k


def test_select_topk_without_ties_and_with_negative_scores():
    scores = jax.random.normal(jax.random.key(0), (5, 64)) - 0.5
    got = sparse_attention.select_topk(scores, jnp.ones_like(scores, bool), 10)
    kth = jnp.sort(scores, axis=-1)[:, -10][:, None]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(scores >= kth))


def _attention_inputs(tokens, rows=2, heads=4, groups=2, d=8, j=2, di=4):
    keys = jax.random.split(jax.random.key(7), 6)
    shapes = ((rows, tokens, heads, d), (rows, tokens, groups, d), (rows, tokens, groups, d),
              (rows, tokens, j, di), (rows, tokens, di), (rows, tokens, j))
    return [jax.random.normal(k, s) for k, s in zip(keys, shapes)]


def test_with_no_more_tokens_than_topk_it_is_dense_causal_attention():
    q, k, v, qi, ki, wi = _attention_inputs(16)
    out, _ = sparse_attention.sparse_attention(q, k, v, qi, ki, wi, topk=16, q_chunk=4)
    rows, tokens, heads, d = q.shape
    kv = jnp.arange(heads) // (heads // k.shape[2])
    logits = jnp.einsum("rthd,rshd->rhts", q, k[:, :, kv]) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((tokens, tokens), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    dense = jnp.einsum("rhts,rshd->rthd", probs, v[:, :, kv]).reshape(rows, tokens, heads * d)
    np.testing.assert_allclose(out, dense, atol=1e-5)


def test_with_more_tokens_than_topk_it_picks_the_references_keys():
    """Chunked program against the reference's block (``lax.top_k`` over all
    of a row's keys): the same output, so the same keys, and the same KL."""
    q, k, v, qi, ki, wi = _attention_inputs(16)
    out, kl = sparse_attention.sparse_attention(q, k, v, qi, ki, wi, topk=6, q_chunk=4)
    want, want_kl = zip(*(reference_tokens._attention_block(
        q[r], k[r], v[r], qi[r], ki[r], wi[r], 0, {"topk": 6}) for r in range(q.shape[0])))
    np.testing.assert_allclose(out, jnp.stack(want), atol=1e-5)
    assert float(kl) == pytest.approx(float(sum(want_kl)), rel=1e-5)
    dense, _ = sparse_attention.sparse_attention(q, k, v, qi, ki, wi, topk=16, q_chunk=4)
    assert rel(out[:, 6:], dense[:, 6:]) > 1e-2  # the selection bites past topk tokens
    np.testing.assert_allclose(out[:, :6], dense[:, :6], atol=1e-5)  # and not before


def test_rope_turns_by_row_and_column_only():
    cos, sin = sparse_attention.rope_tables(4, 8, 1e7, (1, 1, 2))
    x = jax.random.normal(jax.random.key(1), (16, 3, 8))
    turned = sparse_attention.apply_rope(x, cos, sin)
    np.testing.assert_allclose(turned[0], x[0], atol=1e-6)  # patch (0, 0)
    # the first slot (pair 0, 4) turns by t = 0 everywhere
    np.testing.assert_allclose(turned[..., [0, 4]], x[..., [0, 4]], atol=1e-6)
    np.testing.assert_allclose(turned, reference_tokens._rotary(
        x, {"mrope_section": [1, 1, 2], "rope_theta": 1e7}, 4), atol=1e-6)


# ---------------------------------------------------------- the expert share


@pytest.fixture(scope="module")
def uncut_layer():
    layer = experts.ExpertLayer(n_experts=16, top_k=4, width=8, held=(0, 16))
    h = jax.random.normal(jax.random.key(2), (3, 10, 12))
    params = layer.init(jax.random.key(5), h)["params"]
    params = dict(params, router=8 * params["router"],
                  **{n: 20 * params[n] for n in ("w_gate", "w_up", "w_down")})
    return layer, params, h


def test_the_eight_shares_add_up_to_the_uncut_layer(uncut_layer):
    layer, params, h = uncut_layer
    whole, stats = layer.apply({"params": params}, h)
    parts = []
    for share in range(8):
        cut = dict(params, **{n: params[n][2 * share: 2 * share + 2]
                              for n in ("w_gate", "w_up", "w_down")})
        out, part = experts.ExpertLayer(n_experts=16, top_k=4, width=8, held=(2 * share, 2)).apply(
            {"params": cut}, h)
        parts.append((out - h, float(part["held_share"])))
        np.testing.assert_allclose(part["load"], stats["load"])  # routed over all, on every chip
    assert float(jnp.linalg.norm(whole - h)) > 1e-2
    np.testing.assert_allclose(sum(p for p, _ in parts), whole - h, atol=1e-5)
    assert sum(s for _, s in parts) == pytest.approx(1.0) == float(stats["held_share"])


def mix_and_grad(params, h, first, count, chunk, provisioned=0, rule="softmax"):
    """``held_mix`` itself, its value and its gradients, for experts ``first
    .. first + count - 1`` of the uncut layer at ``chunk`` rows a trip, routed
    by ``rule`` (the second under a bias that moves the choice)."""
    b = h.reshape(-1, h.shape[-1])
    bias = 0.2 * jax.random.normal(jax.random.key(8), (params["router"].shape[1],))
    _, top_e, gates = experts.route(b @ params["router"], 4, rule, bias, 2.446)
    held = [params[n][first: first + count] for n in ("w_gate", "w_up", "w_down")]

    def f(b, gates, *w):
        return jnp.sum(jnp.sin(experts.held_mix(b, top_e, gates, *w, first=first, chunk=chunk,
                                                provisioned=provisioned)[0]))

    return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(b, gates, *held)


@pytest.mark.parametrize("rule", ["softmax", "sigmoid"])
@pytest.mark.parametrize("chunk,provisioned", [(3, 0), (6, 0), (12, 0), (16, 0), (1000, 0),
                                               (120, 120)])
def test_no_token_is_dropped_whatever_the_chunk(uncut_layer, chunk, provisioned, rule):
    """Values and gradients do not move with the length of the loop: 40, 20,
    10 and 8 trips over the 120 assignments, one that holds them all, or the
    provision in one trip; under either rule of the router."""
    _, params, h = uncut_layer
    want = mix_and_grad(params, h, 0, 16, 120, rule=rule)
    got = mix_and_grad(params, h, 0, 16, chunk, provisioned, rule)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def _as_bfloat16(a):
    """``a`` rounded to bfloat16, in float32."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def default_precision_dot(a, w):
    """What a TPU's matrix product computes at default precision, written out:
    both operands rounded to bfloat16, their products summed in float32; in
    the backward pass two such products of the cotangent, which is rounded as
    an operand of each and nowhere else."""
    return jnp.dot(_as_bfloat16(a), _as_bfloat16(w), precision="highest")


def _default_precision_dot_bwd(kept, d):
    a, w = map(_as_bfloat16, kept)
    d = _as_bfloat16(d)
    return jnp.dot(d, w.T, precision="highest"), jnp.dot(a.T, d, precision="highest")


default_precision_dot.defvjp(lambda a, w: (default_precision_dot(a, w), (a, w)),
                             _default_precision_dot_bwd)


def plain_mix(b, top_e, gates, w_gate, w_up, w_down, first, chunk=None, provisioned=0,
              product=None):
    """``experts.held_mix``'s result by its definition: every held expert over
    every token through ``default_precision_dot``, weighed by the token's gate
    for that expert (zero where it did not choose it); no sort, no loop over
    rows, no grouped product."""
    y = jnp.zeros_like(b)
    for e in range(w_gate.shape[0]):
        chosen = top_e == first + e
        hidden = (jax.nn.silu(default_precision_dot(b, w_gate[e]))
                  * default_precision_dot(b, w_up[e]))
        y = y + default_precision_dot(hidden, w_down[e]) * jnp.sum(
            jnp.where(chosen, gates, 0.0), axis=-1, keepdims=True)
    return y, jnp.sum((top_e >= first) & (top_e < first + w_gate.shape[0]), dtype=jnp.int32)


@pytest.mark.parametrize("shared_width", [0, 12])
@pytest.mark.parametrize("rule", ["softmax", "sigmoid"])
def test_bfloat16_products_round_each_operand_once_and_no_cotangent(monkeypatch, rule,
                                                                    shared_width):
    """The layer whose grouped products read bfloat16 operands (what
    ``train.supcon.build`` gives a float32 layer on a TPU) is the float32
    layer with every operand of those products rounded where it is made: its
    result and every gradient are ``plain_mix``'s to the order of the sums.
    No cotangent is rounded on its way: every gradient is float32 and holds
    what bfloat16 cannot. The chunk is 16 rows: two trips over the held
    assignments."""
    attrs = dict(n_experts=8, top_k=2, width=16, held=(2, 4), router=rule, gate_scale=2.446,
                 shared_width=shared_width)
    h = jax.random.normal(jax.random.key(3), (2, 24, 32))
    weigh = jax.random.normal(jax.random.key(4), h.shape)
    variables = experts.ExpertLayer(**attrs).init(jax.random.key(5), h)
    params = dict(variables["params"])
    params.update(router=8 * params["router"], **{
        n: 12 * params[n] for n in params if n.startswith(("w_", "shared_"))})
    rest = {k: v for k, v in variables.items() if k != "params"}
    monkeypatch.setattr(experts, "balanced_chunk_rows", lambda *a: 16)

    def value_and_grads(layer):
        def loss(params, h):
            return jnp.sum(layer.apply({**rest, "params": params}, h)[0] * weigh)
        return jax.value_and_grad(loss, argnums=(0, 1))(params, h)

    got = value_and_grads(experts.ExpertLayer(product_dtype=jnp.bfloat16, **attrs))
    exact = value_and_grads(experts.ExpertLayer(**attrs))
    monkeypatch.setattr(experts, "held_mix", plain_mix)
    want = value_and_grads(experts.ExpertLayer(**attrs))
    paths = [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(want)]
    for path, a, b, c in zip(paths, *map(jax.tree.leaves, (got, want, exact))):
        assert a.dtype == jnp.float32, path
        assert rel(a, b) < 2e-6, (path, rel(a, b))
        if a.ndim and "shared" not in path:  # the rounding is there to be seen
            assert rel(a, c) > 1e-4, (path, rel(a, c))
            assert float(jnp.mean(a != _as_bfloat16(a))) > 0.9, path


def test_a_chunk_is_as_many_rows_as_the_trip_budget_holds():
    """``experts.TRIP_BYTES`` beside the nine weight-sized tensors of a trip,
    in whole tiles, evened out over the rows the layer sweeps."""
    spec, assignments = TOKEN_ENCODERS[REAL], 8 * 4096 * 8  # the benchmark's step
    widths = (spec.hidden, spec.expert_width)
    provisioned = experts.provisioned_rows(assignments, 16, 128, spec.capacity_factor)
    rows = experts.balanced_chunk_rows(assignments, 16, 128, provisioned, *widths, jnp.float32)
    assert rows == 32768 and rows % 512 == 0 and provisioned == 2 * rows
    # nothing provisioned: no more than the balanced share
    assert experts.balanced_chunk_rows(assignments, 16, 128, 0, *widths, jnp.float32) == 32768
    assert experts.balanced_chunk_rows(32, 4, 8, 0, 32, 16, jnp.float32) == 512  # never under a tile
    # under one budget 2-byte rows are more rows; the trips are equal, the
    # last one short of full by less than a tile a trip
    few, many = (experts.balanced_chunk_rows(2 ** 22, 16, 128, 2 ** 20, *widths, dtype)
                 for dtype in (jnp.float32, jnp.bfloat16))
    assert few == 36352 < many and few % 512 == 0 == many % 512
    assert 0 <= -(-2 ** 20 // few) * few - 2 ** 20 < 512 * -(-2 ** 20 // few)
    held = 9 * 16 * 2048 * 768 * 4 + 3 * (2048 + 768) * 4 * few  # what the budget counts
    assert held <= experts.TRIP_BYTES < held + 3 * (2048 + 768) * 4 * 512


def sweep_trips(rows, chunk):
    return int(experts._sweep(lambda start, trips: trips + 1, jnp.int32(0), rows, chunk))


def test_the_loop_is_as_long_as_the_held_assignments(uncut_layer):
    """A share of two experts gets about 15 of the 120 assignments: with
    chunks of 4 rows and nothing provisioned the loop makes as many trips as
    they fill and no more, the last one partly over rows that are no held
    assignment."""
    _, params, h = uncut_layer
    b = h.reshape(-1, h.shape[-1])
    _, top_e, gates = experts.route(b @ params["router"], 4)
    held = [params[n][:2] for n in ("w_gate", "w_up", "w_down")]
    n_held = int(experts.held_mix(b, top_e, gates, *held, first=0, chunk=4)[1])
    assert 0 < n_held < 3 * 16
    text = jax.jit(lambda *a: experts.held_mix(*a, first=0, chunk=4)[0]).lower(
        b, top_e, gates, *held).as_text()
    assert text.count("stablehlo.while") == 1  # one loop, no second phase
    assert sweep_trips(n_held, 4) == -(-n_held // 4)
    for a, c in zip(jax.tree.leaves(mix_and_grad(params, h, 0, 2, 4)),
                    jax.tree.leaves(mix_and_grad(params, h, 0, 2, 1000))):
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("provisioned,trips",
                         [(0, None), (8, None), (30, 8), (32, 8), (10 ** 6, 30)])
def test_the_loop_sweeps_what_is_provisioned_whatever_the_routing(uncut_layer, provisioned, trips):
    """Under the provisioned rows every routing makes the same trips (rows
    past the held assignments enter as zeros); above them the trips are the
    data's; the values and gradients are the same either way, and one loop
    does both."""
    _, params, h = uncut_layer
    b = h.reshape(-1, h.shape[-1])
    _, top_e, gates = experts.route(b @ params["router"], 4)
    held = [params[n][:2] for n in ("w_gate", "w_up", "w_down")]
    mix = lambda *a: experts.held_mix(*a, first=0, chunk=4, provisioned=provisioned)  # noqa: E731
    n_held = int(mix(b, top_e, gates, *held)[1])
    assert 8 < n_held < 30
    rows = jnp.maximum(n_held, min(provisioned, top_e.size))
    assert sweep_trips(rows, 4) == (trips or -(-n_held // 4))
    assert jax.jit(lambda *a: mix(*a)[0]).lower(b, top_e, gates, *held).as_text().count(
        "stablehlo.while") == 1

    def f(b, gates, *w):
        return jnp.sum(jnp.sin(mix(b, top_e, gates, *w)[0]))

    got = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(b, gates, *held)
    for a, c in zip(jax.tree.leaves(got), jax.tree.leaves(mix_and_grad(params, h, 0, 2, 1000))):
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-6)


def test_provisioned_rows_are_capacity_factor_balanced_shares():
    assert experts.provisioned_rows(8 * 4096 * 8, 16, 128, 2.0) == 65536  # the benchmark's step
    assert experts.provisioned_rows(8 * 4096 * 8, 16, 128, 0.0) == 0
    assert experts.provisioned_rows(256, 4, 8, 2.0) == 256  # never more than there are
    # the configuration's file states what the preset provisions
    assert (TOKEN_ENCODERS[REAL].capacity_factor
            == reference_tokens.arch(REAL)["expert_capacity_factor"] == 2.0)


def test_all_load_on_one_held_expert_is_served(uncut_layer):
    """The worst skew: every token's first choice is expert 0."""
    layer, params, h = uncut_layer
    skewed = dict(params, router=params["router"].at[:, 0].set(0.0))
    pull = jnp.zeros_like(h).at[..., 0].set(50.0)
    skewed["router"] = skewed["router"].at[0, 0].set(50.0)
    out, stats = experts.ExpertLayer(n_experts=16, top_k=4, width=8, held=(0, 2)).apply(
        {"params": dict(skewed, **{n: params[n][:2] for n in ("w_gate", "w_up", "w_down")})},
        h + pull)
    assert float(stats["load"][0]) == pytest.approx(0.25)  # one of every token's four
    assert bool(jnp.all(jnp.isfinite(out)))


# ------------------------------------------------ the protocol and the ResNets


@pytest.mark.parametrize("name,leaves,stats", [("resnet18", 64, 40), ("resnet50", 163, 106)])
def test_resnet_trees_are_what_they_were(name, leaves, stats):
    """Through ``build_encoder`` a ResNet gets every flag it declares: the
    tree of ``SupConResNet`` is the constructor's own under ``encoder``."""
    model = SupConResNet(model_name=name, remat=True, sync_bn=False, bn_local_groups=2)
    x = jnp.zeros((2, 8, 8, 3))
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), x, train=True))
    direct = jax.eval_shape(lambda: MODEL_DICT[name][0](
        remat=True, sync_bn=False, bn_local_groups=2, bn_group_views=2).init(
            jax.random.key(0), x, train=True))
    assert jax.tree.structure(v["params"]["encoder"]) == jax.tree.structure(direct["params"])
    assert jax.tree.leaves(v["params"]["encoder"]) == jax.tree.leaves(direct["params"])
    assert len(jax.tree.leaves(v["params"])) == leaves
    assert len(jax.tree.leaves(v["batch_stats"])) == stats
    assert infer_architecture_from_variables(v) == (name, "mlp", 128)
    assert model.aux_metric_keys == () and model.encoder_dim == MODEL_DICT[name][1]


@pytest.mark.parametrize("name", ["resnet18", "resnet50"])
def test_resnet_op_names_are_what_they_were(name):
    model = SupConResNet(model_name=name)
    x = jnp.zeros((2, 8, 8, 3))
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), x, train=True))
    text = jax.jit(lambda v, x: model.apply(v, x, train=True, mutable=["batch_stats"])).lower(
        v, x).as_text(debug_info=True)
    for scope in ("SupConResNet/encoder/conv1", "SupConResNet/encoder/bn1",
                  "SupConResNet/encoder/layer1_block0/Conv_0",
                  "SupConResNet/encoder/layer4_block1/", "SupConResNet/proj_head/fc2"):
        assert scope in text, scope


def test_build_encoder_hands_each_encoder_the_flags_it_declares():
    flags = dict(dtype=jnp.bfloat16, remat=True, sync_bn=False, pointwise_bwd=False)
    rn = build_encoder("resnet18", **flags)
    assert (rn.sync_bn, rn.remat, rn.dtype) == (False, True, jnp.bfloat16)
    tok = build_encoder(TINY, **flags)
    assert isinstance(tok, token_encoder.TokenEncoder) and tok.spec is TOKEN_ENCODERS[TINY]
    assert (tok.remat, tok.dtype) == (True, jnp.bfloat16) and not hasattr(tok, "sync_bn")
    assert resnet.tail_bwd_plan(TINY, 8, None, **flags) == []  # no site


def test_token_encoder_tree_is_recognised_and_named_for_the_adapter():
    model = SupConResNet(model_name=TINY, head="linear", feat_dim=32)
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, 16, 16, 3))))
    assert infer_architecture_from_variables(v) == (TINY, "linear", 32)
    assert model.encoder_dim == 32 and model.aux_metric_keys == token_encoder.AUX_METRIC_KEYS
    stats = adapter_tokens.to_reference(v["batch_stats"])
    assert list(reference_tokens.stats_order(TINY)) == sorted(
        stats, key=lambda n: (n.split("/")[0], n.endswith("load_mean")))


def test_program_and_reference_state_the_same_widths():
    """``TOKEN_ENCODERS`` against the configuration's file and the
    reference's tiny preset: one table each, no third."""
    keys = {"patch": "patch_size", "hidden": "hidden_size", "layers": "num_hidden_layers",
            "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
            "head_dim": "head_dim", "index_heads": "indexer_num_heads",
            "index_dim": "indexer_head_dim", "topk": "topk", "rope_theta": "rope_theta",
            "n_experts": "num_experts", "top_k": "num_experts_per_tok",
            "expert_width": "moe_intermediate_size", "balance_coef": "balance_coef",
            "index_coef": "index_coef"}
    for name in (TINY, REAL):
        spec, stated = TOKEN_ENCODERS[name], reference_tokens.arch(name)
        assert {k: getattr(spec, k) for k in keys} == {k: stated[v] for k, v in keys.items()}
        assert list(spec.held) == stated["experts_held"]
        assert list(spec.mrope_section) == stated["mrope_section"]
        assert sparse_attention.RMS_EPS == stated["rms_norm_eps"]


# ------------------------------------------------------------ the train step


def test_config_refuses_an_unknown_model_and_a_size_off_the_patch_grid(tmp_path):
    base = ["--dataset", "synthetic", "--workdir", str(tmp_path), "--batch_size", "4"]
    with pytest.raises(ValueError, match="no encoder"):
        config_lib.parse_supcon(base + ["--model", "resnet51"])
    with pytest.raises(ValueError, match="patches"):
        config_lib.parse_supcon(base + ["--model", TINY, "--size", "18"])
    assert config_lib.parse_supcon(base + ["--model", TINY, "--size", "16"]).model == TINY


def test_recipe_carries_the_encoders_ring_columns(tmp_path):
    """``attach_for_config`` is where they are decided, from the model object:
    after the recipe's own for a token encoder, none for a ResNet; and the
    readers that hold a ring take them back out of its layout."""
    from simclr_pytorch_distributed_tpu.train.supcon_step import extra_columns, metric_keys

    base = ["--dataset", "synthetic", "--workdir", str(tmp_path), "--batch_size", "4",
            "--size", "16", "--recipe", "vicreg"]
    own = recipes_lib.recipe_metric_keys("vicreg")
    for name, sown in ((TINY, token_encoder.AUX_METRIC_KEYS), ("resnet10", ())):
        cfg = config_lib.parse_supcon(base + ["--model", name])
        model = SupConResNet(model_name=name)
        assert model.aux_metric_keys == sown
        assert recipes_lib.build_recipe(cfg).metric_keys == own
        state = types.SimpleNamespace(params=None, batch_stats=None)
        _, recipe = recipes_lib.attach_for_config(cfg, model, state)
        assert recipe.metric_keys == own + sown
        layout = metric_keys(health=True, online_probe=True, extra=recipe.metric_keys)
        assert set(extra_columns(layout)) == set(own + sown)


@pytest.fixture(scope="module")
def one_step(tmp_path_factory):
    """One update through ``train.supcon.build`` and ``make_fused_update``."""
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


def test_build_says_what_the_expert_layers_hold(one_step):
    plans = [r for r in one_step["events"] if r["name"] == "expert_plan"]
    assert len(plans) == 1 and plans[0]["track"] == "compile"
    assert plans[0]["args"] == {"layers": 2, "held": 4, "first": 0, "n_experts": 8,
                                "per_token": 2, "rows_per_step": 8 * 16,
                                "capacity_factor": 2.0, "provisioned_assignments": 8 * 16 * 2,
                                "rows_per_trip": 8 * 16 * 2, "provisioned_trips": 1,
                                "dense_layers": 0, "router": "softmax", "shared_width": 0,
                                "shared_gate": False, "product_operands": "float32",
                                "product_reason": "non-TPU backend (cpu)",
                                "ring_columns": list(token_encoder.AUX_METRIC_KEYS)}


def test_step_writes_the_encoders_columns_and_moves_the_statistics(one_step):
    ring = one_step["ring"]
    assert set(token_encoder.AUX_METRIC_KEYS) <= set(ring)
    assert 0.0 < ring["moe_held_share"] < 1.0 and ring["indexer_kl"] > 0.0
    assert ring["moe_load_max_over_mean"] >= 1.0 and np.isfinite(ring["loss"])
    stats = one_step["state"].batch_stats["encoder"]["block1"]
    assert float(jnp.sum(stats["load_mean"])) == pytest.approx(0.1, rel=1e-5)
    assert float(jnp.sum(stats["prob_mean"])) == pytest.approx(0.1, rel=1e-5)
    assert all(not np.any(leaf) for leaf in jax.tree.leaves(one_step["before"]))  # from rest


@pytest.mark.parametrize("scope", [
    r"encoder/block0/attn/", r"encoder/block1/attn/[^\"]*indexer/", r"encoder/block0/moe/",
    r"encoder/block1/moe/[^\"]*experts/", r"transpose\(jvp\(SupConResNet\)\)/encoder/block0/"])
def test_step_names_the_new_scopes(one_step, scope):
    """Loops and checkpoints put their own components between a module's
    path and a scope inside it; the innermost scope is what the readers take."""
    assert re.search(scope, one_step["text"]), scope


# ------------------------------------------------------------- trace_report


def test_trace_report_prints_the_expert_plan_and_the_ring_columns():
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "scripts"))
    import trace_report

    span = {"name": "flush_boundary", "track": "main:flush", "ph": "X", "ts": 0.0, "dur": 1.0,
            "args": {}}
    plan = {"layers": 4, "held": 16, "first": 0, "n_experts": 128, "per_token": 8,
            "rows_per_step": 32768, "capacity_factor": 2.0, "provisioned_assignments": 65536,
            "rows_per_trip": 32768, "provisioned_trips": 2,
            "product_operands": "bfloat16", "product_reason": None,
            "ring_columns": ["moe_held_share", "moe_load_max_over_mean"]}
    events = [span,
              {"name": "expert_plan", "track": "compile", "ph": "i", "ts": 0.1, "args": plan},
              {"name": "health_window", "track": "health", "ph": "i", "ts": 0.5,
               "args": {"moe_held_share": 0.124, "moe_load_max_over_mean": 1.3, "step": 10}}]
    report = trace_report.build_report(events)
    assert report["encoder"] == {"expert_plan": plan, "ring": {
        "moe_held_share": 0.124, "moe_load_max_over_mean": 1.3}}
    table = trace_report.render_table(report)
    assert "experts: 4 layers hold 16 of 128, 8 a token" in table
    assert ("65536 assignments a layer swept whatever the routing in 2 trips of 32768 rows, "
            "grouped products on bfloat16 operands; moe_held_share") in table
    assert "encoder" not in trace_report.build_report([span])  # a ResNet's run
