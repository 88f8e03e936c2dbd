"""Plain reference of the pretrain step over patch tokens through
Moonlight-16B-A3B's block (``model_type: deepseek_v3``): what
``configs/moonlight-16b-a3b-ep8.json`` states, written straight down in
``jax.numpy`` and float32.

It imports nothing of the program and takes nothing the program has made.
Augmentation, NT-Xent and the schedule are ``reference.py``'s, by import; the
encoder is here. Non-overlapping patches in raster order through a linear
embedding, then ``num_hidden_layers`` pre-norm layers, each

- multi-head latent attention: ``q = a W_q`` in heads of ``qk_nope_head_dim +
  qk_rope_head_dim``; ``a W_kva`` gives a latent of ``kv_lora_rank``, which is
  RMS-normed and expanded by ``W_kvb`` to every head's ``k_n`` and ``v``, and
  ONE rotary key that all heads share; ``q_r`` and the rotary key turn by
  the token's raster index (pairs ``(2m, 2m + 1)``, ``theta ** (-m / (d/2))``);
  full causal softmax attention at ``1 / sqrt(192)``; ``W_o``;
- then, in the first ``first_k_dense_replace`` layers, a dense gated MLP of
  ``intermediate_size``, and in the others the experts: ``s = sigmoid(b
  W_r)``, the ``num_experts_per_tok`` experts of largest ``s + route_bias``
  (the bias chooses and never weighs), gates ``routed_scaling_factor * s /
  (sum of the chosen s + 1e-20)``, this chip's share of the routed experts,
  and the shared experts (one gated MLP of ``n_shared_experts *
  moe_intermediate_size``) for every token;

a final RMS norm, the mean over the tokens, the projection head. The loss is
NT-Xent plus, an expert layer, ``balance_coef`` times the sequence-wise
balance term (for row ``r``: ``f[r, e] = E / (k T) * count_r(e)``, ``P[r, e]
= mean_t s[t, e] / sum_e' s[t, e']``; the rows' mean of ``sum_e f P``);
gradients of ``loss / ngpu``; SGD with momentum and weight decay. After a
train step's forward ``route_bias[e] += bias_update_rate * sign(1 / E -
load[e])`` with ``load`` the share of the step's assignments that chose ``e``;
no gradient reaches it. ``prob_mean`` (the mean of ``s / sum s``) and
``load_mean`` move with ``bn_momentum``.

Departures from the published model, all stated in the configuration's
``assumed``: a patch embedding where the token embedding was, no output head,
mean pooling, the bias's rate and the balance term's form and weight
(DeepSeek-V3's report, whose method ``noaux_tc`` and ``seq_aux`` name), and
the turned rotary pairs left in place (the published code lays them out as
halves: one permutation of ``q_r`` and ``k_r`` alike, which no score sees).

Precision, as the configuration states it: float32 everywhere, every product
at the device's default precision, but the router's logits, which are float32
at ``highest`` so that a choice does not flip on operand rounding.

Written for clarity, not speed: no kernels, no cache, no sorting of tokens by
expert. It is blocked only so that it fits beside what the harness keeps on
the chip: attention and the dense MLP a row at a time (attention a block of
queries at a time against all of the row's keys, causal mask), every held
expert over every token with its gate as a mask, each layer under
``jax.checkpoint``, the step's state donated from step to step, and the first
gradient kept on the host while the later steps run.

The widths come from the configuration's own file (``architecture``); the
tiny preset that the tests and rehearsals use is written down beside it.
"""

from __future__ import annotations

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

import reference as base

HERE = os.path.dirname(os.path.abspath(__file__))
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512

TINY = {
    "patch_size": 4, "hidden_size": 32, "num_hidden_layers": 2, "first_k_dense_replace": 1,
    "intermediate_size": 48, "num_attention_heads": 4, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8, "rope_theta": 5e4,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "n_shared_experts": 2, "shared_intermediate_size": 24, "routed_scaling_factor": 2.446,
    "experts_held": [0, 4], "rms_norm_eps": 1e-5, "bias_update_rate": 0.001,
    "balance_coef": 1e-4,
}


@functools.lru_cache(maxsize=None)
def arch(model: str) -> dict:
    if model == "moonlight-tiny":
        return TINY
    with open(os.path.join(HERE, "configs", f"{model}.json")) as f:
        return json.load(f)["architecture"]


def feature_dim(model: str) -> int:
    return arch(model)["hidden_size"]


def _expert_layers(a: dict):
    return range(a["first_k_dense_replace"], a["num_hidden_layers"])


def running_names(model: str):
    """Every running statistic, in the order of the forward pass."""
    return [f"layer{i}/{s}" for i in _expert_layers(arch(model))
            for s in ("prob_mean", "load_mean", "route_bias")]


def stats_order(model: str):
    """The running statistics that the harness compares, in the order of the
    forward pass: each expert layer's ``prob_mean``, a smooth forward
    quantity. ``load_mean`` and ``route_bias`` count discrete choices: a
    handful of top-k choices that flip on rounding move a ``load_mean`` by
    1e-3 of its length on a sound run, and a ``route_bias`` component flips
    its sign wherever an expert's count sits within those few of a balanced
    share (one sound run in four on the chip), which reads 0.08-0.16 on that
    leaf and moves the median of all leaves by a rank. Both are held to the
    reference where the products are exact (tests/test_latent_encoder.py)."""
    return [name for name in running_names(model) if name.endswith("/prob_mean")]


def param_spec(model: str, feat_dim: int = 128):
    """name -> (shape, init): ``normal`` is a normal of deviation 0.02,
    ``one`` / ``zero`` constants, ``lin<fan_in>`` uniform within
    1/sqrt(fan_in) (the projection head, as in ``reference.py``)."""
    a = arch(model)
    d, h, r = a["hidden_size"], a["num_attention_heads"], a["kv_lora_rank"]
    dn, dr, dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    e, f, fs, fd = (a["num_experts"], a["moe_intermediate_size"],
                    a["shared_intermediate_size"], a["intermediate_size"])
    held = a["experts_held"][1]
    spec = {"embed/w": ((a["patch_size"] ** 2 * 3, d), "normal"), "embed/b": ((d,), "zero")}
    for i in range(a["num_hidden_layers"]):
        p = f"layer{i}"
        spec.update({
            f"{p}/norm1": ((d,), "one"), f"{p}/wq": ((d, h * (dn + dr)), "normal"),
            f"{p}/wkv_a": ((d, r + dr), "normal"), f"{p}/kv_norm": ((r,), "one"),
            f"{p}/wkv_b": ((r, h * (dn + dv)), "normal"), f"{p}/wo": ((h * dv, d), "normal"),
            f"{p}/norm2": ((d,), "one"),
        })
        if i < a["first_k_dense_replace"]:
            spec.update({f"{p}/mlp_gate": ((d, fd), "normal"), f"{p}/mlp_up": ((d, fd), "normal"),
                         f"{p}/mlp_down": ((fd, d), "normal")})
            continue
        spec.update({
            f"{p}/router": ((d, e), "normal"),
            f"{p}/w_gate": ((held, d, f), "normal"), f"{p}/w_up": ((held, d, f), "normal"),
            f"{p}/w_down": ((held, f, d), "normal"),
            f"{p}/shared_gate": ((d, fs), "normal"), f"{p}/shared_up": ((d, fs), "normal"),
            f"{p}/shared_down": ((fs, d), "normal"),
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


def _rotary(x, theta):
    """``x [T, heads, d]`` at positions ``t = 0 .. T - 1``: slot ``m`` of ``d /
    2`` pairs dimension ``2m`` with ``2m + 1`` and turns them by ``t * theta
    ** (-m / (d / 2))``."""
    tokens, _, d = x.shape
    angle = (np.arange(tokens, dtype=np.float32)[:, None]
             * theta ** (-np.arange(d // 2, dtype=np.float32) / (d // 2)))
    cos, sin = jnp.asarray(np.cos(angle))[:, None, :], jnp.asarray(np.sin(angle))[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _attention_block(q, k, v, first):
    """Queries ``first ..`` of one row against all of its keys, head by head:
    ``q [Q, H, d]``, ``k [S, H, d]``, ``v [S, H, dv]`` -> ``[Q, H * dv]``."""
    n_q, heads, d = q.shape
    causal = jnp.arange(k.shape[0])[None, :] <= (first + jnp.arange(n_q))[:, None]
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v).reshape(n_q, -1)


def _attention(p, layer, x, a):
    """``x [R, T, D]`` (the residual stream) -> ``x + attention``."""
    tokens = x.shape[1]
    heads, r = a["num_attention_heads"], a["kv_lora_rank"]
    dn, dr, dv = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    eps, theta = a["rms_norm_eps"], a["rope_theta"]
    w = lambda name: p[f"{layer}/{name}"]  # noqa: E731
    normed = _rms(x, w("norm1"), eps)
    block = QUERY_BLOCK if tokens % QUERY_BLOCK == 0 else tokens

    def one_row(row):
        q = jnp.dot(row, w("wq")).reshape(tokens, heads, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], theta)], axis=-1)
        c = jnp.dot(row, w("wkv_a"))
        kv = jnp.dot(_rms(c[:, :r], w("kv_norm"), eps), w("wkv_b")).reshape(tokens, heads, dn + dv)
        k_r = _rotary(c[:, None, r:], theta)  # one head, for all
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (tokens, heads, dr))], axis=-1)
        v = kv[..., dn:]

        @jax.checkpoint
        def one_block(first):
            return _attention_block(jax.lax.dynamic_slice_in_dim(q, first, block), k, v, first)

        outs = jax.lax.map(one_block, jnp.arange(0, tokens, block))
        return jnp.dot(outs.reshape(tokens, heads * dv), w("wo"))

    return x + jax.lax.map(jax.checkpoint(one_row), normed)


def _dense(p, layer, x, a):
    """``x [R, T, D]`` -> ``x + the dense MLP``, a row at a time."""
    w = lambda name: p[f"{layer}/{name}"]  # noqa: E731
    one_row = lambda row: _gated(  # noqa: E731
        _rms(row, w("norm2"), a["rms_norm_eps"]), w("mlp_gate"), w("mlp_up"), w("mlp_down"))
    return x + jax.lax.map(jax.checkpoint(one_row), x)


def _experts(p, layer, x, bias, a, held=None):
    """``x [R, T, D]``, the router's ``bias [E]`` -> ``(x + the held experts'
    part of the mix + the shared experts, balance term, load [E], mean
    probability [E])``. ``held`` overrides the configuration's ``(first,
    count)``, whose weights ``p`` then has: the tests' way to another share."""
    rows, tokens, d = x.shape
    b = _rms(x, p[f"{layer}/norm2"], a["rms_norm_eps"]).reshape(-1, d)
    n_experts, per_token = a["num_experts"], a["num_experts_per_tok"]
    first, count = held or a["experts_held"]
    s = jax.nn.sigmoid(jnp.dot(b, p[f"{layer}/router"], precision=HIGHEST))
    # E_t: the per_token experts of largest s + bias; lax.top_k puts the lower
    # index first among equals
    _, top_e = jax.lax.top_k(s + bias, per_token)
    chosen = top_e[:, :, None] == jnp.arange(n_experts)[None, None, :]  # [N, k, E]
    picked = jnp.any(chosen, axis=1)  # [N, E]
    gate_of = (a["routed_scaling_factor"] * jnp.where(picked, s, 0.0)
               / (jnp.sum(jnp.where(picked, s, 0.0), axis=-1, keepdims=True) + 1e-20))
    share = s / jnp.sum(s, axis=-1, keepdims=True)
    load = jnp.sum(picked, axis=0).astype(jnp.float32) / (b.shape[0] * per_token)
    prob = jnp.mean(share, axis=0)
    # sequence-wise: f and P of each row, their product summed, the rows' mean
    f_row = (jnp.sum(picked.reshape(rows, tokens, n_experts), axis=1).astype(jnp.float32)
             * n_experts / (per_token * tokens))
    p_row = jnp.mean(share.reshape(rows, tokens, n_experts), axis=1)
    balance = jnp.mean(jnp.sum(f_row * p_row, axis=-1))

    @jax.checkpoint
    def one_expert(e):
        return gate_of[:, first + e, None] * _gated(
            b, p[f"{layer}/w_gate"][e], p[f"{layer}/w_up"][e], p[f"{layer}/w_down"][e])

    y, _ = jax.lax.scan(lambda y, e: (y + one_expert(e), None), jnp.zeros_like(b),
                        jnp.arange(count))
    y = y + _gated(b, p[f"{layer}/shared_gate"], p[f"{layer}/shared_up"],
                   p[f"{layer}/shared_down"])
    return x + y.reshape(x.shape), balance, load, prob


def forward(p, views, model: str, running=None):
    """[N, H, W, 3] views -> ([N, feat_dim] unnormalised projections, the
    auxiliary loss, every expert layer's routing statistics). ``running``
    holds each router's bias (zeros where it is None)."""
    a = arch(model)
    n, height, width, _ = views.shape
    side = a["patch_size"]
    patches = views.reshape(n, height // side, side, width // side, side, 3)
    patches = patches.transpose(0, 1, 3, 2, 4, 5).reshape(n, -1, side * side * 3)
    x = jnp.dot(patches, p["embed/w"]) + p["embed/b"]
    aux, stats = 0.0, {}
    for i in range(a["num_hidden_layers"]):
        layer = f"layer{i}"
        x = jax.checkpoint(functools.partial(_attention, layer=layer, a=a))(p, x=x)
        if i < a["first_k_dense_replace"]:
            x = jax.checkpoint(functools.partial(_dense, layer=layer, a=a))(p, x=x)
            continue
        bias = (jnp.zeros((a["num_experts"],), jnp.float32) if running is None
                else running[f"{layer}/route_bias"])
        x, balance, load, prob = jax.checkpoint(
            functools.partial(_experts, layer=layer, a=a))(p, x=x, bias=bias)
        aux = aux + a["balance_coef"] * balance
        stats[f"{layer}/prob_mean"], stats[f"{layer}/load_mean"] = prob, load
    pooled = jnp.mean(_rms(x, p["final_norm"], a["rms_norm_eps"]), axis=1)
    hidden = jax.nn.relu(jnp.dot(pooled, p["head/fc1/w"]) + p["head/fc1/b"])
    return jnp.dot(hidden, p["head/fc2/w"]) + p["head/fc2/b"], aux, stats


def step_running(running, stats, model: str, momentum: float):
    """The running statistics after a train step whose forward gave
    ``stats``: the two means move with ``momentum``, each router's bias by
    ``bias_update_rate`` towards the experts under a balanced load."""
    a = arch(model)
    out = {}
    for name, value in running.items():
        layer, stat = name.rsplit("/", 1)
        if stat == "route_bias":
            out[name] = value + a["bias_update_rate"] * jnp.sign(
                1.0 / a["num_experts"] - stats[f"{layer}/load_mean"])
        else:
            out[name] = (1.0 - momentum) * value + momentum * stats[name]
    return out


# --------------------------------------------------------------- training


def make_step(model: str, hp: dict, resize_precision=None, drop_half: bool = False):
    """One training step, ``(params, momentum, running, images_u8, key, lr) ->
    (params, momentum, running, loss)``. ``drop_half`` and
    ``resize_precision`` are ``reference.make_step``'s, for ``control.py``."""

    def loss_fn(p, views, running):
        if drop_half:
            b = views.shape[0] // 2
            views = jnp.concatenate([views[: b // 2], views[b: b + b // 2]])
        feats, aux, stats = forward(p, views, model, running)
        loss = base.nt_xent(feats, hp["temp"], hp["base_temperature"]) + aux
        return loss / hp["grad_div"], (loss, stats)

    def step(params, mom, running, images_u8, key, lr):
        views = base.two_views(key, images_u8, hp["size"], hp["mean"], hp["std"],
                               resize_precision)
        grads, (loss, stats) = jax.grad(loss_fn, has_aux=True)(params, views, running)
        mom = jax.tree.map(lambda m, g, p: hp["momentum"] * m + g + hp["weight_decay"] * p,
                           mom, grads, params)
        params = jax.tree.map(lambda p, m: p - lr * m, params, mom)
        return params, mom, step_running(running, stats, model, hp["bn_momentum"]), loss

    return step


@functools.lru_cache(maxsize=None)
def _programs(model, hp_items, resize_precision, drop_half, shardings):
    hp = dict(hp_items)
    one = make_step(model, hp, resize_precision, drop_half)

    def step(params, mom, running, batches, key, k, lr):
        return one(params, mom, running, batches[k], jax.random.fold_in(key, k), lr)

    # a copy of the weights to step on, no momentum, the statistics at rest
    start = lambda p: (jax.tree.map(jnp.copy, p), jax.tree.map(jnp.zeros_like, p),  # noqa: E731
                       running_at_rest(p))
    minus = lambda a, b: jax.tree.map(jnp.subtract, a, b)  # noqa: E731
    # the first gradient, from the momentum after the first step: g + wd * p0
    first_grad = lambda mom, p0: jax.tree.map(  # noqa: E731
        lambda m, p: m - hp["weight_decay"] * p, mom, p0)
    how = {}
    if shardings is not None:
        repl, rows = shardings
        how = dict(in_shardings=(repl, repl, repl, rows, repl, repl, repl), out_shardings=repl)
    return (jax.jit(step, donate_argnums=(0, 1, 2), **how), jax.jit(start), jax.jit(minus),
            jax.jit(first_grad))


def trajectory(params, batches_u8, base_key, model, hp, steps=3, resize_precision=None,
               drop_half=False, shardings=None):
    """``reference.trajectory``'s contract: the losses of the first ``steps``
    steps from ``params``, the first gradient, the parameters' change over
    all the steps, the compared running statistics' change over the first
    step, and ``stats_order``. The state is donated from step to step and the first
    gradient is handed over as host arrays, so beside ``params`` there live
    on the device one copy of the weights, the momentum and what a step
    needs."""
    jstep, jstart, jminus, jfirst_grad = _programs(
        model, tuple(sorted(hp.items())), resize_precision, drop_half, shardings)
    p, mom, running = jstart(params)
    losses, grad, stats = [], None, None
    for k in range(steps):
        p, mom, running, loss = jstep(p, mom, running, batches_u8, base_key, np.int32(k),
                                      np.float32(base.learning_rate(k, hp)))
        losses.append(loss)
        if k == 0:
            # the first gradient waits on the host while the next steps run:
            # one copy of the weights less on the device beside them
            grad = jax.device_get(jfirst_grad(mom, params))
            at_rest = init_running(params)
            stats = jminus({k: running[k] for k in at_rest}, at_rest)
    del mom
    return {"losses": [float(v) for v in jax.device_get(losses)],
            "grad": grad, "change": jminus(p, params), "stats": stats,
            "stats_order": stats_order(model)}
