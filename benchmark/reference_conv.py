"""Plain reference of the pretrain step over patch tokens through
LFM2-8B-A1B's block (``model_type: lfm2_moe``): what
``configs/lfm2-8b-a1b-ep4.json`` states, written straight down in
``jax.numpy`` and float32.

It imports nothing of the program and takes nothing the program has made.
Augmentation, NT-Xent and the schedule are ``reference.py``'s, by import; the
encoder is here. Non-overlapping patches in raster order through a linear
embedding, then ``num_hidden_layers`` pre-norm layers. Layer ``i`` mixes its
tokens as ``layer_types[i]`` says:

- ``conv``, the gated short convolution: ``[B, C, x] = a W_bcx`` (columns in
  that order, ``hidden_size`` each); ``u[t] = sum_j w[j] (B x)[t - L + 1 +
  j]`` over the ``conv_L_cache`` taps ``j``, zero before the first token, no
  bias and no activation; ``(C u) W_o``;
- ``full_attention``: ``q = a W_q`` in ``num_attention_heads``, ``k = a
  W_k``, ``v = a W_v`` in ``num_key_value_heads``, all of ``head_dim``; q and
  k RMS-normed per head and turned whole by the rotary embedding
  (rotate-half pairs ``(m, m + d / 2)`` by ``t * rope_theta ** (-m / (d /
  2))``, ``t`` the token's raster index); causal softmax at ``1 /
  sqrt(head_dim)``; ``W_o``. No output gate;

then, in the first ``num_dense_layers`` layers, a dense gated MLP of
``intermediate_size`` and in the others the experts: ``s = sigmoid(b W_r)``
over all ``num_experts_routed_over``, the ``num_experts_per_tok`` experts of
largest ``s + route_bias`` (the bias chooses and never weighs), gates
``routed_scaling_factor * s / (sum of the chosen s + 1e-6)``
(``norm_topk_prob``), this chip's share of the routed experts, and no shared
expert. A final RMS norm, the mean over the tokens, the projection head. The
loss is NT-Xent alone: the configuration names no balance term. Gradients
of ``loss / ngpu``; SGD with momentum and weight decay. After a train step's
forward ``route_bias[e] += bias_update_rate * sign(1 / E - load[e])`` with
``load`` the share of the step's assignments that chose ``e``; no gradient
reaches it. ``prob_mean`` (the mean of ``s / sum s``) and ``load_mean`` move
with ``bn_momentum``. The running statistics, the three steps' programs and
their driver are ``reference_latent``'s (``step_running``, ``make_step``,
``_programs``, ``trajectory``), read with this file's model.

Departures from the published model, all stated in the configuration's
``assumed``: a patch embedding where the token embedding was, no output head,
mean pooling, the bias's rate (DeepSeek-V3's, whose method
``use_expert_bias`` names), and the layers kept (published layers 0 and 2-5).

Precision, as the configuration states it: float32 everywhere, every product
at the device's default precision, but the router's logits, which are float32
at ``highest`` so that a choice does not flip on operand rounding. (A
reference whose products are exact where the program's are not stands as far
from a sound run as from one in lower precision: PERF.md, "How ``correct`` is
decided".)

Written for clarity, not speed: no kernels, no sorting of tokens by expert,
no capacity. It is blocked only so that it fits beside what the harness keeps
on the chip: each mixer and the dense MLP a row at a time, attention a block
of queries at a time against all of the row's keys, every held expert over
every token with its gate as a mask, each layer under ``jax.checkpoint``, the
step's state donated from step to step, and the first gradient kept on the
host while the later steps run.

The widths come from the configuration's own file (``architecture``); the
tiny preset that the tests and rehearsals use is written down beside it.
"""

from __future__ import annotations

import functools
import json
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np

import reference as base
import reference_latent

HERE = os.path.dirname(os.path.abspath(__file__))
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
GATE_EPS = 1e-6  # the published denominator of the normalised gates

TINY = {
    "patch_size": 4, "hidden_size": 32, "num_hidden_layers": 5,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"], "conv_L_cache": 3,
    "num_dense_layers": 1, "intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "rope_theta": 1e6, "num_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16, "routed_scaling_factor": 1.0,
    "experts_held": [0, 4], "rms_norm_eps": 1e-5, "bias_update_rate": 0.001,
}


@functools.lru_cache(maxsize=None)
def arch(model: str) -> dict:
    if model == "lfm2-tiny":
        return TINY
    with open(os.path.join(HERE, "configs", f"{model}.json")) as f:
        return json.load(f)["architecture"]


def feature_dim(model: str) -> int:
    return arch(model)["hidden_size"]


def _expert_layers(a: dict):
    return range(a["num_dense_layers"], a["num_hidden_layers"])


def running_names(model: str):
    """Every running statistic, in the order of the forward pass."""
    return [f"layer{i}/{s}" for i in _expert_layers(arch(model))
            for s in ("prob_mean", "load_mean", "route_bias")]


def stats_order(model: str):
    """The running statistics that the harness compares, in the order of the
    forward pass: each expert layer's ``prob_mean``, a smooth forward
    quantity. ``load_mean`` and ``route_bias`` count discrete choices that
    flip on rounding in a sound run (``reference_latent.stats_order``); both
    are held to the reference where the products are exact
    (tests/test_conv_encoder.py)."""
    return [name for name in running_names(model) if name.endswith("/prob_mean")]


def param_spec(model: str, feat_dim: int = 128):
    """name -> (shape, init): ``normal`` is a normal of deviation 0.02,
    ``one`` / ``zero`` constants, ``lin<fan_in>`` uniform within
    1/sqrt(fan_in) (the projection head, as in ``reference.py``, and the
    convolution)."""
    a = arch(model)
    d, heads, kv, hd = (a["hidden_size"], a["num_attention_heads"], a["num_key_value_heads"],
                        a["head_dim"])
    taps, e, f, fd = (a["conv_L_cache"], a["num_experts"], a["moe_intermediate_size"],
                      a["intermediate_size"])
    held = a["experts_held"][1]
    spec = {"embed/w": ((a["patch_size"] ** 2 * 3, d), "normal"), "embed/b": ((d,), "zero")}
    for i, kind in enumerate(a["layer_types"]):
        p = f"layer{i}"
        spec.update({f"{p}/norm1": ((d,), "one"), f"{p}/norm2": ((d,), "one")})
        if kind == "conv":
            spec.update({f"{p}/w_bcx": ((d, 3 * d), "normal"),
                         f"{p}/conv": ((taps, d), f"lin{taps}"), f"{p}/wo": ((d, d), "normal")})
        else:
            spec.update({
                f"{p}/wq": ((d, heads * hd), "normal"), f"{p}/wk": ((d, kv * hd), "normal"),
                f"{p}/wv": ((d, kv * hd), "normal"), f"{p}/wo": ((heads * hd, d), "normal"),
                f"{p}/q_norm": ((hd,), "one"), f"{p}/k_norm": ((hd,), "one"),
            })
        if i < a["num_dense_layers"]:
            spec.update({f"{p}/mlp_gate": ((d, fd), "normal"), f"{p}/mlp_up": ((d, fd), "normal"),
                         f"{p}/mlp_down": ((fd, d), "normal")})
            continue
        spec.update({
            f"{p}/router": ((d, e), "normal"),
            f"{p}/w_gate": ((held, d, f), "normal"), f"{p}/w_up": ((held, d, f), "normal"),
            f"{p}/w_down": ((held, f, d), "normal"),
        })
    spec["final_norm"] = ((d,), "one")
    spec["head/fc1/w"] = ((d, d), f"lin{d}")
    spec["head/fc1/b"] = ((d,), f"lin{d}")
    spec["head/fc2/w"] = ((d, feat_dim), f"lin{d}")
    spec["head/fc2/b"] = ((feat_dim,), f"lin{d}")
    return spec


def init_params(key, model: str, feat_dim: int = 128):
    """All weights from one key, in float32, each array from its own fold of
    the key (in the order of the sorted names)."""
    params = {}
    for n, (name, (shape, init)) in enumerate(sorted(param_spec(model, feat_dim).items())):
        k = jax.random.fold_in(key, n)
        if init == "normal":
            params[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
        elif init == "one":
            params[name] = jnp.ones(shape, jnp.float32)
        elif init == "zero":
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            bound = 1.0 / math.sqrt(int(init[3:]))
            params[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    return params


def running_at_rest(params):
    """Every running statistic before the first step: all zero, the routers'
    biases among them."""
    running = {}
    for name, w in params.items():
        if name.endswith("/router"):
            layer = name[: -len("/router")]
            for stat in ("prob_mean", "load_mean", "route_bias"):
                running[f"{layer}/{stat}"] = jnp.zeros((w.shape[1],), jnp.float32)
    return running


def init_running(params):
    """Of ``running_at_rest``, what the harness compares (``stats_order``)."""
    return {k: v for k, v in running_at_rest(params).items() if k.endswith("/prob_mean")}


# ------------------------------------------------------------------ model


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _gated(x, w_gate, w_up, w_down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up), w_down)


def _conv_mixer(p, layer, x, a):
    """``x [R, T, D]`` (the residual stream) -> ``x + the gated short
    convolution``."""
    tokens, d = x.shape[1], x.shape[2]
    taps = a["conv_L_cache"]
    w = lambda name: p[f"{layer}/{name}"]  # noqa: E731

    def one_row(row):
        bcx = jnp.dot(row, w("w_bcx"))
        b, c, xs = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
        bx = b * xs
        # tap j reads the token L - 1 - j places back
        u = sum(w("conv")[j] * jnp.concatenate([jnp.zeros((taps - 1 - j, d)),
                                                bx[: tokens - (taps - 1 - j)]])
                for j in range(taps))
        return jnp.dot(c * u, w("wo"))

    return x + jax.lax.map(jax.checkpoint(one_row), _rms(x, w("norm1"), a["rms_norm_eps"]))


def _turn(x, theta):
    """``x [T, heads, d]`` at positions ``t = 0 .. T - 1``: rotate-half pairs
    ``(m, m + d / 2)`` turned by ``t * theta ** (-m / (d / 2))``."""
    tokens, half = x.shape[0], x.shape[-1] // 2
    angle = (np.arange(tokens, dtype=np.float32)[:, None]
             * theta ** (-np.arange(half, dtype=np.float32) / half))
    cos, sin = jnp.asarray(np.cos(angle))[:, None, :], jnp.asarray(np.sin(angle))[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention_block(q, k, v, first):
    """Queries ``first ..`` of one row against all of its keys, head by head:
    ``q [Q, H, d]``, ``k``, ``v`` ``[S, H, d]`` -> ``[Q, H, d]``."""
    causal = jnp.arange(k.shape[0])[None, :] <= (first + jnp.arange(q.shape[0]))[:, None]
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v)


def _attention(p, layer, x, a):
    """``x [R, T, D]`` (the residual stream) -> ``x + attention``."""
    tokens = x.shape[1]
    heads, kv, hd = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    eps, theta = a["rms_norm_eps"], a["rope_theta"]
    w = lambda name: p[f"{layer}/{name}"]  # noqa: E731
    kv_head = np.arange(heads) // (heads // kv)
    block = QUERY_BLOCK if tokens % QUERY_BLOCK == 0 else tokens

    def one_row(row):
        q = _turn(_rms(jnp.dot(row, w("wq")).reshape(tokens, heads, hd), w("q_norm"), eps), theta)
        k = _turn(_rms(jnp.dot(row, w("wk")).reshape(tokens, kv, hd), w("k_norm"), eps),
                  theta)[:, kv_head]
        v = jnp.dot(row, w("wv")).reshape(tokens, kv, hd)[:, kv_head]

        @jax.checkpoint
        def one_block(first):
            return _attention_block(jax.lax.dynamic_slice_in_dim(q, first, block), k, v, first)

        outs = jax.lax.map(one_block, jnp.arange(0, tokens, block))
        return jnp.dot(outs.reshape(tokens, heads * hd), w("wo"))

    return x + jax.lax.map(jax.checkpoint(one_row), _rms(x, w("norm1"), eps))


def _dense(p, layer, x, a):
    """``x [R, T, D]`` -> ``x + the dense MLP``, a row at a time."""
    w = lambda name: p[f"{layer}/{name}"]  # noqa: E731
    one_row = lambda row: _gated(  # noqa: E731
        _rms(row, w("norm2"), a["rms_norm_eps"]), w("mlp_gate"), w("mlp_up"), w("mlp_down"))
    return x + jax.lax.map(jax.checkpoint(one_row), x)


def _experts(p, layer, x, bias, a, held=None):
    """``x [R, T, D]``, the router's ``bias [E]`` -> ``(x + the held experts'
    part of the mix, load [E], mean probability [E])``. ``held`` overrides
    the configuration's ``(first, count)``, whose weights ``p`` then has: the
    tests' way to another share."""
    d = x.shape[-1]
    b = _rms(x, p[f"{layer}/norm2"], a["rms_norm_eps"]).reshape(-1, d)
    n_experts, per_token = a["num_experts"], a["num_experts_per_tok"]
    first, count = held or a["experts_held"]
    s = jax.nn.sigmoid(jnp.dot(b, p[f"{layer}/router"], precision=HIGHEST))
    # the per_token experts of largest s + bias; lax.top_k puts the lower
    # index first among equals
    _, top_e = jax.lax.top_k(s + bias, per_token)
    picked = jnp.any(top_e[:, :, None] == jnp.arange(n_experts)[None, None, :], axis=1)
    chosen = jnp.where(picked, s, 0.0)
    gate_of = (a["routed_scaling_factor"] * chosen
               / (jnp.sum(chosen, axis=-1, keepdims=True) + GATE_EPS))
    load = jnp.sum(picked, axis=0).astype(jnp.float32) / (b.shape[0] * per_token)
    prob = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)

    @jax.checkpoint
    def one_expert(e):
        return gate_of[:, first + e, None] * _gated(
            b, p[f"{layer}/w_gate"][e], p[f"{layer}/w_up"][e], p[f"{layer}/w_down"][e])

    y, _ = jax.lax.scan(lambda y, e: (y + one_expert(e), None), jnp.zeros_like(b),
                        jnp.arange(count))
    return x + y.reshape(x.shape), load, prob


def forward(p, views, model: str, running=None):
    """[N, H, W, 3] views -> ([N, feat_dim] unnormalised projections, the
    auxiliary loss (none: 0), every expert layer's routing statistics).
    ``running`` holds each router's bias (zeros where it is None)."""
    a = arch(model)
    n, height, width, _ = views.shape
    side = a["patch_size"]
    patches = views.reshape(n, height // side, side, width // side, side, 3)
    patches = patches.transpose(0, 1, 3, 2, 4, 5).reshape(n, -1, side * side * 3)
    x = jnp.dot(patches, p["embed/w"]) + p["embed/b"]
    stats = {}
    for i, kind in enumerate(a["layer_types"]):
        layer = f"layer{i}"
        mixer = _conv_mixer if kind == "conv" else _attention
        x = jax.checkpoint(functools.partial(mixer, layer=layer, a=a))(p, x=x)
        if i < a["num_dense_layers"]:
            x = jax.checkpoint(functools.partial(_dense, layer=layer, a=a))(p, x=x)
            continue
        bias = (jnp.zeros((a["num_experts"],), jnp.float32) if running is None
                else running[f"{layer}/route_bias"])
        x, load, prob = jax.checkpoint(
            functools.partial(_experts, layer=layer, a=a))(p, x=x, bias=bias)
        stats[f"{layer}/prob_mean"], stats[f"{layer}/load_mean"] = prob, load
    pooled = jnp.mean(_rms(x, p["final_norm"], a["rms_norm_eps"]), axis=1)
    hidden = jax.nn.relu(jnp.dot(pooled, p["head/fc1/w"]) + p["head/fc1/b"])
    return jnp.dot(hidden, p["head/fc2/w"]) + p["head/fc2/b"], 0.0, stats


# --------------------------------------------------------------- training


def _with_our_names(fn):
    """``reference_latent``'s function ``fn``, reading this file's ``arch``,
    ``forward``, ``make_step``, ``step_running``, ``running_at_rest``,
    ``init_running`` and ``stats_order``."""
    return types.FunctionType(fn.__code__, globals(), fn.__name__, fn.__defaults__)


# the bias's sign step and the two means' momentum: ``reference_latent``'s
step_running = _with_our_names(reference_latent.step_running)
# one training step, ``(params, momentum, running, images_u8, key, lr) ->
# (params, momentum, running, loss)``
make_step = _with_our_names(reference_latent.make_step)
# the three steps' programs and their driver: ``trajectory``'s contract is
# ``reference.trajectory``'s
_programs = functools.lru_cache(maxsize=None)(
    _with_our_names(reference_latent._programs.__wrapped__))
trajectory = _with_our_names(reference_latent.trajectory)
