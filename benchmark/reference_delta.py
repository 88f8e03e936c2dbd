"""Plain reference of the pretrain step over patch tokens through
Qwen3-Next-80B-A3B's block (``model_type: qwen3_next``): what
``configs/qwen3-next-80b-a3b-ep32.json`` states, written straight down in
``jax.numpy`` and float32.

It imports nothing of the program and takes nothing the program has made.
Augmentation, NT-Xent and the schedule are ``reference.py``'s, by import; the
encoder is here. Non-overlapping patches in raster order through a linear
embedding, then ``num_hidden_layers`` pre-norm layers. Layer ``i`` mixes its
tokens with gated full attention where ``(i + 1) % full_attention_interval
== 0`` and with Gated DeltaNet otherwise:

- Gated DeltaNet: ``[q, k, v, z] = a W_qkvz``, ``[b, c] = a W_ba``; ``q``,
  ``k``, ``v`` through a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps and ``silu``; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(c + dt_bias)``; ``q``, ``k`` L2-normalised, ``q`` over
  ``sqrt(dk)``, value head ``h`` on key head ``h // (Hv / Hk)``; per value
  head the delta rule TOKEN BY TOKEN: ``S = exp(g_t) S``, ``S += k_t
  (beta_t (v_t - S^T k_t))^T``, ``o_t = S^T q_t``, from ``S = 0``; ``rms(o)
  w silu(z)`` per head; ``W_out``;
- gated attention: ``a W_q`` per head ``[query | gate]``, ``k``, ``v`` in
  ``num_key_value_heads``; q and k RMS-normed per head and their first
  ``rotary_dim`` dimensions turned (rotate-half, raster index, ``rope_theta``);
  causal softmax at ``1 / sqrt(head_dim)``; times ``sigmoid(gate)``; ``W_o``;

then softmax routing over all ``num_experts``, the ``num_experts_per_tok``
of largest probability, gates renormalised over those, this chip's share of
the routed experts, and the shared expert (a gated MLP of
``shared_expert_intermediate_size``) for every token, scaled by
``sigmoid(b w_s)``. A final RMS norm, the mean over the tokens, the
projection head. The loss is NT-Xent plus, a layer, ``balance_coef`` times
``E * sum(load * mean probability)``; gradients of ``loss / ngpu``; SGD with
momentum and weight decay. ``prob_mean`` and ``load_mean`` move with
``bn_momentum``.

Departures from the published model, all stated in the configuration's
``assumed``: a patch embedding where the token embedding was, no output head
and no multi-token prediction, mean pooling, norms ``x / rms(x) * w`` with
``w`` at 1 (published: ``* (1 + w)`` with ``w`` at 0, the same function at
initialisation), the balance term's form, and the columns of ``W_qkvz`` and
``W_ba`` laid out part by part where the published checkpoint groups them by
key head (a permutation of the same columns).

Precision, as the configuration states it: float32 everywhere, every product
at the device's default precision, but the router's logits, which are float32
at ``highest`` so that a choice does not flip on operand rounding. (A
reference whose products are exact where the program's are not stands as far
from a sound run as from one in lower precision: PERF.md, "How ``correct`` is
decided".)

Written for clarity, not speed: no kernels, no chunks of the recurrence, no
sorting of tokens by expert. It is blocked only so that it fits beside what
the harness keeps on the chip: each mixer a row at a time, the recurrence
under ``jax.checkpoint`` a segment of ``SEGMENT`` tokens at a time, attention
a block of queries at a time against all of the row's keys, every held
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
import types

import jax
import jax.numpy as jnp
import numpy as np

import reference as base
import reference_latent

HERE = os.path.dirname(os.path.abspath(__file__))
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
SEGMENT = 64  # tokens of the recurrence between two saved states

TINY = {
    "patch_size": 4, "hidden_size": 32, "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8, "rotary_dim": 2,
    "rope_theta": 1e7, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "chunk_size": 4, "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "experts_held": [0, 4], "rms_norm_eps": 1e-6,
    "balance_coef": 0.001,
}


@functools.lru_cache(maxsize=None)
def arch(model: str) -> dict:
    if model == "qwen3-next-tiny":
        return TINY
    with open(os.path.join(HERE, "configs", f"{model}.json")) as f:
        return json.load(f)["architecture"]


def feature_dim(model: str) -> int:
    return arch(model)["hidden_size"]


def is_full(a: dict, layer: int) -> bool:
    """Whether layer ``layer`` mixes with full attention (else Gated DeltaNet)."""
    return (layer + 1) % a["full_attention_interval"] == 0


def running_names(model: str):
    """Every running statistic, in the order of the forward pass."""
    return [f"layer{i}/{s}" for i in range(arch(model)["num_hidden_layers"])
            for s in ("prob_mean", "load_mean")]


def stats_order(model: str):
    """The running statistics that the harness compares, in the order of the
    forward pass: each layer's ``prob_mean``, a smooth forward quantity.
    ``load_mean`` counts discrete top-k choices, a handful of which flip on
    rounding in a sound run (PERF.md, section 6); it is held to the
    reference where the products are exact (tests/test_delta_encoder.py)."""
    return [name for name in running_names(model) if name.endswith("/prob_mean")]


def param_spec(model: str, feat_dim: int = 128):
    """name -> (shape, init): ``normal`` is a normal of deviation 0.02,
    ``one`` / ``zero`` constants, ``lin<fan_in>`` uniform within
    1/sqrt(fan_in) (the projection head, as in ``reference.py``, and the
    convolution), ``alog`` the log of a uniform draw in (0, 16)."""
    a = arch(model)
    d, heads, kv, hd = (a["hidden_size"], a["num_attention_heads"], a["num_key_value_heads"],
                        a["head_dim"])
    hk, hv, dk, dv = (a["linear_num_key_heads"], a["linear_num_value_heads"],
                      a["linear_key_head_dim"], a["linear_value_head_dim"])
    taps, mixed = a["linear_conv_kernel_dim"], 2 * hk * dk + hv * dv
    e, f, fs, held = (a["num_experts"], a["moe_intermediate_size"],
                      a["shared_expert_intermediate_size"], a["experts_held"][1])
    spec = {"embed/w": ((a["patch_size"] ** 2 * 3, d), "normal"), "embed/b": ((d,), "zero")}
    for i in range(a["num_hidden_layers"]):
        p = f"layer{i}"
        spec[f"{p}/norm1"] = ((d,), "one")
        if is_full(a, i):
            spec.update({
                f"{p}/wq": ((d, heads * 2 * hd), "normal"), f"{p}/wk": ((d, kv * hd), "normal"),
                f"{p}/wv": ((d, kv * hd), "normal"), f"{p}/wo": ((heads * hd, d), "normal"),
                f"{p}/q_norm": ((hd,), "one"), f"{p}/k_norm": ((hd,), "one"),
            })
        else:
            spec.update({
                f"{p}/w_qkvz": ((d, mixed + hv * dv), "normal"), f"{p}/w_ba": ((d, 2 * hv), "normal"),
                f"{p}/conv": ((taps, mixed), f"lin{taps}"), f"{p}/A_log": ((hv,), "alog"),
                f"{p}/dt_bias": ((hv,), "one"), f"{p}/out_norm": ((dv,), "one"),
                f"{p}/wo": ((hv * dv, d), "normal"),
            })
        spec.update({
            f"{p}/norm2": ((d,), "one"), f"{p}/router": ((d, e), "normal"),
            f"{p}/w_gate": ((held, d, f), "normal"), f"{p}/w_up": ((held, d, f), "normal"),
            f"{p}/w_down": ((held, f, d), "normal"),
            f"{p}/shared_gate": ((d, fs), "normal"), f"{p}/shared_up": ((d, fs), "normal"),
            f"{p}/shared_down": ((fs, d), "normal"), f"{p}/shared_expert_gate": ((d, 1), "normal"),
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
        elif init == "alog":
            params[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1e-4, 16.0))
        else:
            bound = 1.0 / math.sqrt(int(init[3:]))
            params[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    return params


def running_at_rest(params):
    """Every running statistic before the first step: all zero."""
    running = {}
    for name, w in params.items():
        if name.endswith("/router"):
            layer = name[: -len("/router")]
            for stat in ("prob_mean", "load_mean"):
                running[f"{layer}/{stat}"] = jnp.zeros((w.shape[1],), jnp.float32)
    return running


def init_running(params):
    """Of ``running_at_rest``, what the harness compares (``stats_order``)."""
    return {k: v for k, v in running_at_rest(params).items() if k.endswith("/prob_mean")}


# ------------------------------------------------------------------ model


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _gated(x, w_gate, w_up, w_down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up), w_down)


def delta_rule(q, k, v, g, beta):
    """One row, token by token: ``q``, ``k`` ``[T, H, dk]``, ``v [T, H, dv]``,
    ``g``, ``beta`` ``[T, H]`` -> ``o [T, H, dv]``. The state is saved once a
    segment of ``SEGMENT`` tokens and recomputed inside it for the backward
    pass."""

    def one_token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    tokens, heads, dk = q.shape
    seg = SEGMENT if tokens % SEGMENT == 0 else tokens
    xs = [x.reshape(tokens // seg, seg, *x.shape[1:]) for x in (q, k, v, g, beta)]
    one_segment = jax.checkpoint(lambda s, x: jax.lax.scan(one_token, s, x))
    _, o = jax.lax.scan(one_segment, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape(tokens, heads, -1)


def _linear_attention(p, layer, x, a):
    """``x [R, T, D]`` (the residual stream) -> ``x + Gated DeltaNet``."""
    tokens = x.shape[1]
    hk, hv, dk, dv = (a["linear_num_key_heads"], a["linear_num_value_heads"],
                      a["linear_key_head_dim"], a["linear_value_head_dim"])
    taps, mixed, eps = a["linear_conv_kernel_dim"], 2 * hk * dk + hv * dv, a["rms_norm_eps"]
    w = lambda name: p[f"{layer}/{name}"]  # noqa: E731
    key_head = np.arange(hv) // (hv // hk)

    def one_row(row):
        qkvz = jnp.dot(row, w("w_qkvz"))
        padded = jnp.concatenate([jnp.zeros((taps - 1, mixed)), qkvz[:, :mixed]])
        conv = jax.nn.silu(sum(padded[j: j + tokens] * w("conv")[j] for j in range(taps)))
        q = _l2(conv[:, : hk * dk].reshape(tokens, hk, dk))[:, key_head] / math.sqrt(dk)
        k = _l2(conv[:, hk * dk: 2 * hk * dk].reshape(tokens, hk, dk))[:, key_head]
        v = conv[:, 2 * hk * dk:].reshape(tokens, hv, dv)
        ba = jnp.dot(row, w("w_ba"))
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(w("A_log")) * jax.nn.softplus(ba[:, hv:] + w("dt_bias"))
        o = delta_rule(q, k, v, g, beta)
        z = qkvz[:, mixed:].reshape(tokens, hv, dv)
        y = _rms(o, w("out_norm"), eps) * jax.nn.silu(z)
        return jnp.dot(y.reshape(tokens, hv * dv), w("wo"))

    return x + jax.lax.map(jax.checkpoint(one_row), _rms(x, w("norm1"), eps))


def _turn(x, theta, dim):
    """``x [T, heads, d]`` at positions ``t = 0 .. T - 1``: its first ``dim``
    dimensions in rotate-half pairs ``(m, m + dim / 2)`` turned by ``t *
    theta ** (-m / (dim / 2))``, the rest as they are."""
    tokens, half = x.shape[0], dim // 2
    angle = (np.arange(tokens, dtype=np.float32)[:, None]
             * theta ** (-np.arange(half, dtype=np.float32) / half))
    cos, sin = jnp.asarray(np.cos(angle))[:, None, :], jnp.asarray(np.sin(angle))[:, None, :]
    x1, x2 = x[..., :half], x[..., half:dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., dim:]], axis=-1)


def _attention_block(q, k, v, first):
    """Queries ``first ..`` of one row against all of its keys, head by head:
    ``q [Q, H, d]``, ``k``, ``v`` ``[S, H, d]`` -> ``[Q, H, d]``."""
    causal = jnp.arange(k.shape[0])[None, :] <= (first + jnp.arange(q.shape[0]))[:, None]
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v)


def _full_attention(p, layer, x, a):
    """``x [R, T, D]`` (the residual stream) -> ``x + gated attention``."""
    tokens = x.shape[1]
    heads, kv, hd = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    eps, theta, dim = a["rms_norm_eps"], a["rope_theta"], a["rotary_dim"]
    w = lambda name: p[f"{layer}/{name}"]  # noqa: E731
    kv_head = np.arange(heads) // (heads // kv)
    block = QUERY_BLOCK if tokens % QUERY_BLOCK == 0 else tokens

    def one_row(row):
        q_gate = jnp.dot(row, w("wq")).reshape(tokens, heads, 2 * hd)
        q = _turn(_rms(q_gate[..., :hd], w("q_norm"), eps), theta, dim)
        k = _turn(_rms(jnp.dot(row, w("wk")).reshape(tokens, kv, hd), w("k_norm"), eps),
                  theta, dim)[:, kv_head]
        v = jnp.dot(row, w("wv")).reshape(tokens, kv, hd)[:, kv_head]

        @jax.checkpoint
        def one_block(first):
            return _attention_block(jax.lax.dynamic_slice_in_dim(q, first, block), k, v, first)

        outs = jax.lax.map(one_block, jnp.arange(0, tokens, block)).reshape(tokens, heads, hd)
        gated = outs * jax.nn.sigmoid(q_gate[..., hd:])
        return jnp.dot(gated.reshape(tokens, heads * hd), w("wo"))

    return x + jax.lax.map(jax.checkpoint(one_row), _rms(x, w("norm1"), eps))


def _experts(p, layer, x, a, held=None):
    """``x [R, T, D]`` -> ``(x + the held experts' part of the mix + the
    gated shared expert, balance term, load [E], mean probability [E])``.
    ``held`` overrides the configuration's ``(first, count)``, whose weights
    ``p`` then has: the tests' way to another share."""
    shape = x.shape
    b = _rms(x, p[f"{layer}/norm2"], a["rms_norm_eps"]).reshape(-1, shape[-1])
    n, n_experts, per_token = b.shape[0], a["num_experts"], a["num_experts_per_tok"]
    first, count = held or a["experts_held"]
    probs = jax.nn.softmax(jnp.dot(b, p[f"{layer}/router"], precision=HIGHEST), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, per_token)
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # [N, E]: a token's gate for each expert, 0 where it did not choose it
    gate_of = jnp.zeros((n, n_experts)).at[jnp.arange(n)[:, None], top_e].add(gates)
    load = jnp.zeros((n_experts,)).at[top_e.reshape(-1)].add(1.0) / (n * per_token)
    prob = jnp.mean(probs, axis=0)
    balance = n_experts * jnp.sum(load * prob)

    @jax.checkpoint
    def one_expert(e):
        return gate_of[:, first + e, None] * _gated(
            b, p[f"{layer}/w_gate"][e], p[f"{layer}/w_up"][e], p[f"{layer}/w_down"][e])

    y, _ = jax.lax.scan(lambda y, e: (y + one_expert(e), None), jnp.zeros_like(b),
                        jnp.arange(count))
    shared = _gated(b, p[f"{layer}/shared_gate"], p[f"{layer}/shared_up"],
                    p[f"{layer}/shared_down"])
    y = y + jax.nn.sigmoid(jnp.dot(b, p[f"{layer}/shared_expert_gate"])) * shared
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
        mixer = _full_attention if is_full(a, i) else _linear_attention
        x = jax.checkpoint(functools.partial(mixer, layer=layer, a=a))(p, x=x)
        x, balance, load, prob = jax.checkpoint(
            functools.partial(_experts, layer=layer, a=a))(p, x=x)
        aux = aux + a["balance_coef"] * balance
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
        running = {k: (1.0 - m) * v + m * stats[k] for k, v in running.items()}
        return params, mom, running, loss

    return step


def _with_our_names(fn):
    """``reference_latent``'s function ``fn``, reading this file's
    ``make_step``, ``running_at_rest``, ``init_running`` and ``stats_order``."""
    return types.FunctionType(fn.__code__, globals(), fn.__name__, fn.__defaults__)


# the three steps' programs and their driver are ``reference_latent``'s:
# ``trajectory``'s contract is ``reference.trajectory``'s
_programs = functools.lru_cache(maxsize=None)(
    _with_our_names(reference_latent._programs.__wrapped__))
trajectory = _with_our_names(reference_latent.trajectory)
