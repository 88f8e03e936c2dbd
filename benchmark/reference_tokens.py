"""Plain reference of the pretrain step over patch tokens: what
``configs/keye-vl2-a3b-ep8.json`` states, written straight down in
``jax.numpy`` and float32.

It imports nothing of the program and takes nothing the program has made.
Augmentation, NT-Xent and the schedule are ``reference.py``'s, by import; the
encoder is here: non-overlapping patches in raster order through a linear
embedding, then ``num_hidden_layers`` pre-norm layers of grouped-query
attention behind a learned top-k key indexer and of softmax-routed experts
(this chip's share of them), a final RMS norm, the mean over the tokens, the
projection head. The loss is NT-Xent plus, a layer, ``balance_coef`` times the
router's balance term and ``index_coef`` times the indexer's KL; gradients of
``loss / ngpu``; SGD with momentum and weight decay.

Precision, as the configuration states it: float32 everywhere, every product
at the device's default precision, but the router's logits and the indexer's
scores, which are float32 at ``highest`` so that a selection does not flip on
operand rounding.

Written for clarity, not speed: no kernels, no cache, no sorting of tokens by
expert. It is blocked only so that it fits beside what the harness keeps on
the chip: attention a row and a block of queries at a time against all of
the row's keys (causal mask), every held expert over every token with its
gate as a mask, each layer under ``jax.checkpoint``, the step's state
donated from step to step, and the first gradient kept on the host while the
later steps run.

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
    "patch_size": 4, "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "indexer_num_heads": 2, "indexer_head_dim": 4,
    "topk": 6, "rope_theta": 1e7, "mrope_section": [1, 1, 2], "num_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16, "experts_held": [0, 4],
    "rms_norm_eps": 1e-6, "balance_coef": 0.001, "index_coef": 1.0,
}


@functools.lru_cache(maxsize=None)
def arch(model: str) -> dict:
    if model == "keye-vl2-tiny":
        return TINY
    with open(os.path.join(HERE, "configs", f"{model}.json")) as f:
        return json.load(f)["architecture"]


def feature_dim(model: str) -> int:
    return arch(model)["hidden_size"]


def stats_order(model: str):
    """The running statistics in the order of the forward pass."""
    return [f"layer{i}/{s}" for i in range(arch(model)["num_hidden_layers"])
            for s in ("prob_mean", "load_mean")]


def param_spec(model: str, feat_dim: int = 128):
    """name -> (shape, init): ``normal`` is a normal of deviation 0.02,
    ``one`` / ``zero`` constants, ``lin<fan_in>`` uniform within
    1/sqrt(fan_in) (the projection head, as in ``reference.py``)."""
    a = arch(model)
    d, h, g, hd = (a["hidden_size"], a["num_attention_heads"], a["num_key_value_heads"],
                   a["head_dim"])
    j, di, e, f = (a["indexer_num_heads"], a["indexer_head_dim"], a["num_experts"],
                   a["moe_intermediate_size"])
    held = a["experts_held"][1]
    spec = {"embed/w": ((a["patch_size"] ** 2 * 3, d), "normal"), "embed/b": ((d,), "zero")}
    for i in range(a["num_hidden_layers"]):
        p = f"layer{i}"
        spec.update({
            f"{p}/norm1": ((d,), "one"), f"{p}/wq": ((d, h * hd), "normal"),
            f"{p}/wk": ((d, g * hd), "normal"), f"{p}/wv": ((d, g * hd), "normal"),
            f"{p}/q_norm": ((hd,), "one"), f"{p}/k_norm": ((hd,), "one"),
            f"{p}/index_wq": ((d, j * di), "normal"), f"{p}/index_wk": ((d, di), "normal"),
            f"{p}/index_ww": ((d, j), "normal"), f"{p}/wo": ((h * hd, d), "normal"),
            f"{p}/norm2": ((d,), "one"), f"{p}/router": ((d, e), "normal"),
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


def init_running(params):
    """The running statistics before the first step: all zero."""
    running = {}
    for name, w in params.items():
        if name.endswith("/router"):
            layer = name[: -len("/router")]
            running[f"{layer}/prob_mean"] = jnp.zeros((w.shape[1],), jnp.float32)
            running[f"{layer}/load_mean"] = jnp.zeros((w.shape[1],), jnp.float32)
    return running


# ------------------------------------------------------------------ model


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotary(x, a, grid):
    """``x [T, heads, head_dim]`` of a ``grid x grid`` raster of patches at
    positions ``(t, i, j) = (0, row, column)``: slot ``m`` of ``head_dim / 2``
    pairs dimension ``m`` with ``m + head_dim / 2`` and turns them by ``pos *
    theta ** (-m / (head_dim / 2))``, where ``pos`` is ``t`` in the first
    ``mrope_section[0]`` slots, ``i`` in the next ``[1]`` and ``j`` in the
    last ``[2]``."""
    half = x.shape[-1] // 2
    s0, s1, _ = a["mrope_section"]
    token = np.arange(grid * grid)
    position = np.zeros((grid * grid, half), np.float32)
    position[:, s0:s0 + s1] = (token // grid)[:, None]
    position[:, s0 + s1:] = (token % grid)[:, None]
    angle = position * (a["rope_theta"] ** (-np.arange(half, dtype=np.float32) / half))
    cos, sin = jnp.asarray(np.cos(angle))[:, None, :], jnp.asarray(np.sin(angle))[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention_block(q, k, v, qi, ki, wi, first, a):
    """Queries ``first ..`` of one row against all of its keys. Returns the
    heads' outputs ``[Q, H * d]`` and the block's sum of the indexer's KL."""
    n_q, heads, d = q.shape
    n_k, groups, _ = k.shape
    topk = min(a["topk"], n_k)
    t = first + jnp.arange(n_q)
    causal = jnp.arange(n_k)[None, :] <= t[:, None]
    # the indexer's score of every key for every query, float32 at highest
    per_head = jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi, ki, precision=HIGHEST))
    index = jnp.einsum("tjs,tj->ts", per_head, wi, precision=HIGHEST)
    index = index / math.sqrt(qi.shape[1] * qi.shape[2])
    # S_t: the topk causal keys of largest score; lax.top_k puts the lower
    # index first among equals
    _, picked = jax.lax.top_k(jnp.where(causal, index, -jnp.inf), topk)
    selected = jnp.zeros((n_q, n_k), bool).at[jnp.arange(n_q)[:, None], picked].set(True)
    selected = selected & causal
    kv_of_head = jnp.arange(heads) // (heads // groups)
    scores = jnp.einsum("thd,shd->hts", q, k[:, kv_of_head]) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(selected[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v[:, kv_of_head]).reshape(n_q, heads * d)
    # KL(pi || softmax of the index scores over S_t), pi held fixed
    pi = jax.lax.stop_gradient(jnp.sum(probs, axis=0))
    pi = pi / jnp.sum(pi, axis=-1, keepdims=True)
    log_index = jax.nn.log_softmax(jnp.where(selected, index, -jnp.inf), axis=-1)
    terms = jnp.where(pi > 0, pi * (jnp.log(jnp.where(pi > 0, pi, 1.0)) - log_index), 0.0)
    return out, jnp.sum(terms)


def _attention(p, layer, x, a):
    """``x [R, T, D]`` (the residual stream) -> ``(x + attention, mean KL)``."""
    rows, tokens, _ = x.shape
    grid = math.isqrt(tokens)
    heads, groups, d = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    j, di = a["indexer_num_heads"], a["indexer_head_dim"]
    w = lambda name: p[f"{layer}/{name}"]  # noqa: E731
    normed = _rms(x, w("norm1"), a["rms_norm_eps"])
    block = QUERY_BLOCK if tokens % QUERY_BLOCK == 0 else tokens

    def one_row(row):
        q = _rms(jnp.dot(row, w("wq")).reshape(tokens, heads, d), w("q_norm"), a["rms_norm_eps"])
        k = _rms(jnp.dot(row, w("wk")).reshape(tokens, groups, d), w("k_norm"), a["rms_norm_eps"])
        v = jnp.dot(row, w("wv")).reshape(tokens, groups, d)
        q, k = _rotary(q, a, grid), _rotary(k, a, grid)
        fixed = jax.lax.stop_gradient(row)
        qi = jnp.dot(fixed, w("index_wq")).reshape(tokens, j, di)
        ki = jnp.dot(fixed, w("index_wk"))
        wi = jnp.dot(fixed, w("index_ww"))

        @jax.checkpoint
        def one_block(first):
            cut = lambda t: jax.lax.dynamic_slice_in_dim(t, first, block)  # noqa: E731
            return _attention_block(cut(q), k, v, cut(qi), ki, cut(wi), first, a)

        outs, kls = jax.lax.map(one_block, jnp.arange(0, tokens, block))
        return jnp.dot(outs.reshape(tokens, heads * d), w("wo")), jnp.sum(kls)

    out, kl = jax.lax.map(jax.checkpoint(one_row), normed)
    return x + out, jnp.sum(kl) / (rows * tokens)


def _experts(p, layer, x, a):
    """``x [R, T, D]`` -> ``(x + the held experts' part of the mix, balance
    term, load [E], mean probability [E])``."""
    shape = x.shape
    b = _rms(x, p[f"{layer}/norm2"], a["rms_norm_eps"]).reshape(-1, shape[-1])
    n_experts, per_token = a["num_experts"], a["num_experts_per_tok"]
    first, count = a["experts_held"]
    probs = jax.nn.softmax(jnp.dot(b, p[f"{layer}/router"], precision=HIGHEST), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, per_token)
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = top_e[:, :, None] == jnp.arange(n_experts)[None, None, :]  # [N, k, E]
    load = jnp.sum(chosen, axis=(0, 1)).astype(jnp.float32) / (b.shape[0] * per_token)
    prob = jnp.mean(probs, axis=0)
    balance = n_experts * jnp.sum(load * prob)
    gate_of = jnp.sum(jnp.where(chosen, gates[:, :, None], 0.0), axis=1)  # [N, E], 0 if not chosen

    @jax.checkpoint
    def one_expert(e):
        hidden = jax.nn.silu(jnp.dot(b, p[f"{layer}/w_gate"][e])) * jnp.dot(b, p[f"{layer}/w_up"][e])
        return gate_of[:, first + e, None] * jnp.dot(hidden, p[f"{layer}/w_down"][e])

    y, _ = jax.lax.scan(lambda y, e: (y + one_expert(e), None), jnp.zeros_like(b),
                        jnp.arange(count))
    return x + y.reshape(shape), balance, load, prob


def forward(p, views, model: str):
    """[N, H, W, 3] views -> ([N, feat_dim] unnormalised projections, the
    auxiliary loss, every layer's routing statistics)."""
    a = arch(model)
    n, height, width, _ = views.shape
    side = a["patch_size"]
    patches = views.reshape(n, height // side, side, width // side, side, 3)
    patches = patches.transpose(0, 1, 3, 2, 4, 5).reshape(n, -1, side * side * 3)
    x = jnp.dot(patches, p["embed/w"]) + p["embed/b"]
    aux, stats = 0.0, {}
    for i in range(a["num_hidden_layers"]):
        layer = f"layer{i}"
        x, kl = jax.checkpoint(functools.partial(_attention, layer=layer, a=a))(p, x=x)
        x, balance, load, prob = jax.checkpoint(
            functools.partial(_experts, layer=layer, a=a))(p, x=x)
        aux = aux + a["balance_coef"] * balance + a["index_coef"] * kl
        stats[f"{layer}/prob_mean"], stats[f"{layer}/load_mean"] = prob, load
    pooled = jnp.mean(_rms(x, p["final_norm"], a["rms_norm_eps"]), axis=1)
    hidden = jax.nn.relu(jnp.dot(pooled, p["head/fc1/w"]) + p["head/fc1/b"])
    return jnp.dot(hidden, p["head/fc2/w"]) + p["head/fc2/b"], aux, stats


# --------------------------------------------------------------- training


def make_step(model: str, hp: dict, resize_precision=None, drop_half: bool = False):
    """One training step, ``(params, momentum, running, images_u8, key, lr) ->
    (params, momentum, running, loss)``. ``drop_half`` and
    ``resize_precision`` are ``reference.make_step``'s, for ``control.py``."""

    def loss_fn(p, views):
        if drop_half:
            b = views.shape[0] // 2
            views = jnp.concatenate([views[: b // 2], views[b: b + b // 2]])
        feats, aux, stats = forward(p, views, model)
        loss = base.nt_xent(feats, hp["temp"], hp["base_temperature"]) + aux
        return loss / hp["grad_div"], (loss, stats)

    def step(params, mom, running, images_u8, key, lr):
        views = base.two_views(key, images_u8, hp["size"], hp["mean"], hp["std"],
                               resize_precision)
        grads, (loss, stats) = jax.grad(loss_fn, has_aux=True)(params, views)
        mom = jax.tree.map(lambda m, g, p: hp["momentum"] * m + g + hp["weight_decay"] * p,
                           mom, grads, params)
        params = jax.tree.map(lambda p, m: p - lr * m, params, mom)
        m = hp["bn_momentum"]
        running = {k: (1.0 - m) * running[k] + m * stats[k] for k in running}
        return params, mom, running, loss

    return step


@functools.lru_cache(maxsize=None)
def _programs(model, hp_items, resize_precision, drop_half, shardings):
    hp = dict(hp_items)
    one = make_step(model, hp, resize_precision, drop_half)

    def step(params, mom, running, batches, key, k, lr):
        return one(params, mom, running, batches[k], jax.random.fold_in(key, k), lr)

    # a copy of the weights to step on, no momentum, the statistics at rest
    start = lambda p: (jax.tree.map(jnp.copy, p), jax.tree.map(jnp.zeros_like, p),  # noqa: E731
                       init_running(p))
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
    all the steps, the running statistics' change over the first step, and
    ``stats_order``. The state is donated from step to step and the first
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
            stats = jminus(running, init_running(params))
    del mom
    return {"losses": [float(v) for v in jax.device_get(losses)],
            "grad": grad, "change": jminus(p, params), "stats": stats,
            "stats_order": stats_order(model)}
