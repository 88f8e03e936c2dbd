"""A layer of routed experts that is told which experts it holds, the
shared experts beside them, and the dense feed-forward layer of the same form.

Expert parallelism's layer as one chip runs it: the router scores every
token over all ``n_experts`` and chooses ``top_k`` of them by one of two
rules (``route``), and this layer computes the part of the result that its
own experts give, ``held = (first, count)``, and what every chip computes
alike for its own rows, the shared experts (``shared_width``, none at 0),
scaled where ``shared_expert_gate`` by ``sigmoid(b_t w_s)`` with ``w_s [D, 1]``
(else by 1):

    y_t = sum over e in (top_k of t) and in held of gate[t, e] * f_e(b_t) + s_t f_sh(b_t)
    f_e(x) = (silu(x W_gate[e]) * (x W_up[e])) W_down[e]

The rules: ``softmax`` takes the ``top_k`` largest probabilities and
renormalises them over those; ``sigmoid`` scores each expert on its own,
chooses the ``top_k`` of largest score plus a load-correcting bias, and
weighs them by ``gate_scale`` times their unbiased scores over those scores'
sum plus ``gate_eps``: the bias chooses and never weighs. The bias is state
and no parameter (``route_bias`` in ``batch_stats``, zeros at rest): in train
mode each step moves it by ``bias_rate`` towards the experts that took less
than a balanced share of this step's assignments, and no gradient reaches it.

What the absent experts would add is left out; no code stands in for the
other chips or their exchange. No token is dropped, whatever the imbalance:
the held assignments are sorted by expert and taken a chunk at a time,
each chunk one grouped product a projection (``lax.ragged_dot``), added back
onto its tokens. A chunk is as many rows as a byte budget holds
(``TRIP_BYTES``, ``balanced_chunk_rows``): what a trip moves that is the
size of the weights and not of its rows (their reads, their gradients'
partial sums) is then paid a few times a layer and not once every few
thousand rows. The loop (``lax.while_loop``) sweeps the rows the layer is
provisioned for, ``capacity_factor`` balanced shares, whatever the routing
(rows past the held assignments enter as zeros), and goes on past them for as
long as held assignments are left: up to that load every step does the same
work, as a deployment's fixed-capacity buffers make it, and the step's time
does not follow which experts a batch happened to favour; above it a step
pays for the rows its routing gave these experts, and nothing is sized for
the worst case. ``capacity_factor`` 0 provisions nothing: the loop is then as
long as the data.

The grouped products read their operands in a type of their own
(``ExpertLayer.product_dtype``, the layer's where None) and give the layer's.
A TPU's product at default precision rounds float32 operands to bfloat16
inside every call, after reading them at four bytes an element; with
bfloat16 given as the products' type the same rounding is done once where
an operand is made (tokens and weights once a sweep, before the loop; a
chunk's intermediates as the elementwise pass that makes them writes them),
inside the custom VJP, so that no cotangent is rounded, and the layer's
results are the same to the bit while every call reads half the bytes.

Beside ``y`` the layer returns what the step and the tracing need of the
routing, over all ``n_experts``: the share of assignments each expert took
(``load``), its mean router probability (``prob``: for ``sigmoid`` the
scores as shares of a token's sum), the balance term (``n_experts * sum(load
* prob)`` over the batch or, ``sequence_balance``, the same product row by
row, averaged over the rows), the share of assignments that landed on held
experts and the bias's largest component.

``DenseLayer`` is the layer a model's leading blocks have in the experts'
place: one ``f`` for every token, a few rows at a time.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from simclr_pytorch_distributed_tpu.models.sparse_attention import (
    HIGHEST,
    RMS_EPS,
    map_row_groups,
    normal_init,
    rms_norm,
    tie_gradients,
)

SCOPE_EXPERTS = "experts"
SCOPE_SHARED = "shared"


# What one trip of the backward sweep may hold at once, in bytes. A trip holds
# nine tensors the size of a held weight matrix whatever its rows (the three
# gradients' partial sums, the trip's own three, the three weights re-laid for
# the input-gradient products) and, a row, about three vectors as wide as a
# token and three as wide as an expert (the chip's compiler keeps 33.8 kB a
# row alive at 2048 x 768 float32, which is this count; it is held to it in
# tests/test_tpu_aot_compile.py). At the benchmark's shapes the budget gives
# 32,768 rows, two trips over the provision; the program with the provision
# in one trip does not load beside the driver's state (PERF.md, PR 31).
TRIP_BYTES = 2 << 30
_TILE = 512  # rows: a chunk is whole tiles of the grouped products


def balanced_chunk_rows(assignments: int, held: int, n_experts: int, provisioned: int,
                        hidden: int, width: int, dtype) -> int:
    """Rows of a chunk of sorted assignments, one trip of the loop: the
    ``provisioned`` rows (the held experts' share of a balanced load where
    nothing is provisioned) in the fewest equal trips whose working set
    ``TRIP_BYTES`` holds, for ``held`` experts of ``hidden x width`` in
    ``dtype``; whole tiles, and never under one."""
    size = jnp.dtype(dtype).itemsize
    fit = (TRIP_BYTES - 9 * held * hidden * width * size) // (3 * (hidden + width) * size)
    sweep = provisioned or assignments * held // n_experts
    trips = max(1, -(-sweep // max(_TILE, fit // _TILE * _TILE)))
    return max(_TILE, -(-sweep // (trips * _TILE)) * _TILE)


def provisioned_rows(assignments: int, held: int, n_experts: int,
                     capacity_factor: float) -> int:
    """Rows every step sweeps: ``capacity_factor`` times the held experts'
    share of a balanced load, and no more than there are."""
    return min(int(capacity_factor * assignments * held / n_experts), assignments)


def route(logits: jax.Array, top_k: int, rule: str = "softmax", bias=None, scale: float = 1.0,
          eps: float = 1e-20):
    """``[N, E]`` float32 router logits -> ``(probabilities [N, E], the chosen
    experts [N, k], their gates [N, k])`` by the module docstring's ``rule``;
    ``sigmoid`` takes the ``bias [E]`` it chooses by, the gates' ``scale`` and
    the ``eps`` their denominator adds, and its probabilities are a token's
    scores over their sum. Ties go to the lower index."""
    if rule == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top_p, top_e = lax.top_k(probs, top_k)
        return probs, top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if rule != "sigmoid":
        raise ValueError(f"no router rule {rule!r}")
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, top_e = lax.top_k(scores + bias, top_k)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    gates = scale * top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + eps)
    return scores / jnp.sum(scores, axis=-1, keepdims=True), top_e, gates


def routing_statistics(probs: jax.Array, top_e: jax.Array):
    """``(load [E], prob [E])``: the share of the ``N * k`` assignments that
    chose each expert, and each expert's mean probability."""
    n_experts = probs.shape[-1]
    counts = jnp.zeros((n_experts,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    return counts / top_e.size, jnp.mean(probs, axis=0)


def sequence_balance(probs: jax.Array, top_e: jax.Array, rows: int):
    """The balance term row by row (DeepSeek-V3's sequence-wise form): for
    each of the ``rows`` sequences that the ``N`` tokens are, ``n_experts *
    sum_e(load_r[e] * prob_r[e])`` over that row's assignments and mean
    probabilities; the rows' mean."""
    n_experts, k = probs.shape[-1], top_e.shape[-1]
    chosen = top_e.reshape(rows, -1)
    counts = jnp.zeros((rows, n_experts), jnp.float32).at[
        jnp.arange(rows)[:, None], chosen].add(1.0)
    prob = jnp.mean(probs.reshape(rows, -1, n_experts), axis=1)
    return jnp.mean(jnp.sum(counts * (n_experts / chosen.shape[1]) * prob, axis=-1))


def gated_mlp(x, w_gate, w_up, w_down):
    """``f(x) = (silu(x W_gate) * (x W_up)) W_down``: an expert's form, for
    weights that every token meets."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _group_sizes(expert, count: int):
    """Rows of a chunk for each of ``count`` held experts, given the rows'
    (local) experts, ``count`` for a row that is no held assignment. Such
    rows come last; they enter as zeros and count as the last expert's, so
    that every chunk is a full one to the grouped products."""
    sizes = jnp.sum(expert[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32)
    return sizes.at[count - 1].add(expert.shape[0] - jnp.sum(sizes))


def _chunk_out(x, w_gate, w_up, w_down, gate, expert):
    """One chunk of sorted assignments: token rows ``x [C, D]``, their gates
    and (local) experts (``_group_sizes``). Rows and weights come in the type
    the grouped products read (``held_mix``'s ``product``); the products'
    results and all arithmetic on them are of the gates' type, the layer's,
    and ``hidden`` is rounded to the products' as it is written."""
    count = w_gate.shape[0]
    sizes = _group_sizes(expert, count)
    x = jnp.where((expert < count)[:, None], x, 0)
    dot = functools.partial(lax.ragged_dot, group_sizes=sizes, preferred_element_type=gate.dtype)
    with jax.named_scope(SCOPE_EXPERTS):
        hidden = (jax.nn.silu(dot(x, w_gate)) * dot(x, w_up)).astype(x.dtype)
        out = dot(hidden, w_down)
    return out * gate[:, None]


# [C, A] x [C, B] -> [count, A, B]: the rows of each group contracted, a
# weight's gradient from a chunk (what ``ragged_dot``'s own transpose takes)
_ROWS_CONTRACTED = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _chunk_back(x, weights, transposed, gate, expert, dy):
    """``_chunk_out``'s backward written out over the same grouped products:
    the chunk recomputed, then ``(dx, [dw_gate, dw_up, dw_down], dgate)`` for
    the cotangent ``dy [C, D]`` of its result. The input-gradient products
    take the weights with their last two axes swapped, ``transposed``, which
    the caller makes once for all chunks (``jax.vjp`` of ``_chunk_out``
    swaps them inside every trip). ``dy`` and the gates are of the layer's
    type, and so is everything returned; ``x`` and the weights of the
    products', to which ``hidden``, ``d_out``, ``d_a`` and ``d_u`` are rounded
    as they are written: where a product at default precision rounds them."""
    w_gate, w_up, w_down = weights
    gate_t, up_t, down_t = transposed
    count = w_gate.shape[0]
    sizes = _group_sizes(expert, count)
    held = (expert < count)[:, None]
    x = jnp.where(held, x, 0)
    as_operand = lambda a: a.astype(x.dtype)  # noqa: E731
    dot = functools.partial(lax.ragged_dot, group_sizes=sizes, preferred_element_type=gate.dtype)
    d_out = as_operand(dy * gate[:, None])
    with jax.named_scope(SCOPE_EXPERTS):
        pre = dot(x, w_gate), dot(x, w_up)
        hidden, back = jax.vjp(lambda a, u: jax.nn.silu(a) * u, *pre)
        hidden = as_operand(hidden)
        out = dot(hidden, w_down)
        d_a, d_u = map(as_operand, back(dot(d_out, down_t)))
        dx = dot(d_a, gate_t) + dot(d_u, up_t)
        dw = [lax.ragged_dot_general(rows, d, sizes, _ROWS_CONTRACTED,
                                     preferred_element_type=gate.dtype)
              for rows, d in ((x, d_a), (x, d_u), (hidden, d_out))]
    return jnp.where(held, dx, 0), dw, jnp.sum(out * dy, axis=-1)


def _sweep(step, carry, rows, chunk: int):
    """``step(start, carry) -> carry`` over the first ``rows`` sorted
    assignments, ``chunk`` rows a trip."""
    return lax.while_loop(lambda c: c[0] < rows,
                          lambda c: (c[0] + chunk, step(c[0], c[1])),
                          (jnp.int32(0), carry))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _mix_sorted(b, w_gate, w_up, w_down, gate, token, expert, rows, chunk, product):
    """``y [N, D]``: the first ``rows`` sorted assignments a chunk at a time
    (``_sweep``), each chunk through ``_chunk_out`` and added onto its tokens.
    The loop's length is not static, so the backward pass is written as the
    same sweep over ``_chunk_back``: it recomputes the chunk and keeps
    nothing. The grouped products read ``b`` and the weights in the type
    ``product``, to which each is rounded here, once a sweep and before the
    loop (a cast before this function would be transposed into a rounding of
    its cotangent)."""
    rounded = b.astype(product)
    weights = [w.astype(product) for w in (w_gate, w_up, w_down)]

    def step(start, y):
        cut = lambda a: lax.dynamic_slice_in_dim(a, start, chunk)  # noqa: E731
        tok = cut(token)
        return y.at[tok].add(_chunk_out(rounded[tok], *weights, cut(gate), cut(expert)))

    return _sweep(step, jnp.zeros_like(b), rows, chunk)


def _mix_sorted_fwd(b, w_gate, w_up, w_down, gate, token, expert, rows, chunk, product):
    return (_mix_sorted(b, w_gate, w_up, w_down, gate, token, expert, rows, chunk, product),
            (b, w_gate, w_up, w_down, gate, token, expert, rows))


def _mix_sorted_bwd(chunk, product, kept, dy):
    b, w_gate, w_up, w_down, gate, token, expert, rows = kept
    rounded = b.astype(product)
    weights = tuple(w.astype(product) for w in (w_gate, w_up, w_down))
    transposed = tuple(jnp.swapaxes(w, 1, 2) for w in weights)  # once, not a trip

    def step(start, carry):
        db, dw, dgate = carry
        cut = lambda a: lax.dynamic_slice_in_dim(a, start, chunk)  # noqa: E731
        tok = cut(token)
        dx, dw_chunk, dg = _chunk_back(rounded[tok], weights, transposed, cut(gate),
                                       cut(expert), dy[tok])
        return (db.at[tok].add(dx), [a + c for a, c in zip(dw, dw_chunk)],
                lax.dynamic_update_slice_in_dim(dgate, dg, start, 0))

    zeros = jnp.zeros_like
    db, dw, dgate = _sweep(step, (zeros(b), [zeros(w) for w in kept[1:4]], zeros(gate)),
                           rows, chunk)
    return (db, *dw, dgate, None, None, None)


_mix_sorted.defvjp(_mix_sorted_fwd, _mix_sorted_bwd)


def held_mix(b, top_e, gates, w_gate, w_up, w_down, first: int, chunk: int,
             provisioned: int = 0, product=None):
    """The held experts' part of the mix for tokens ``b [N, D]``: weights
    ``[count, D, F]``, ``[count, D, F]``, ``[count, F, D]`` of experts
    ``first .. first + count - 1``, ``chunk`` sorted assignments a trip of
    the loop (``balanced_chunk_rows`` has the size a layer takes), which
    sweeps ``provisioned`` rows (to the end of their chunk) at the least.
    ``product`` is the type of the grouped products' operands, ``b``'s where
    None; their results, ``y`` and every gradient are of ``b``'s type
    whatever it is. Returns ``(y [N, D], held assignments)``."""
    N, k = top_e.shape
    count = w_gate.shape[0]
    local = top_e.reshape(-1) - first
    expert = jnp.where((local >= 0) & (local < count), local, count)  # count: not held
    order = jnp.argsort(expert, stable=True)  # held assignments first, by expert
    n_held = jnp.sum(expert < count, dtype=jnp.int32)
    chunk = min(chunk, N * k)
    pad = -(N * k) % chunk
    y = _mix_sorted(
        b, w_gate, w_up, w_down,
        jnp.pad(gates.reshape(-1)[order].astype(b.dtype), (0, pad)),
        jnp.pad(order // k, (0, pad)),
        jnp.pad(expert[order], (0, pad), constant_values=count),
        jnp.maximum(n_held, min(provisioned, N * k)), chunk,
        jnp.dtype(b.dtype if product is None else product))
    return y, n_held


class ExpertLayer(nn.Module):
    """The layer with its pre-norm and its residual: router over
    ``n_experts``, the ``held`` experts' weights and the shared experts'.
    Takes tokens ``h [R, T, D]``; returns ``(h + y, statistics)`` with the
    module docstring's ``load``, ``prob``, ``balance``, ``held_share`` and,
    where the router has a bias, ``bias_max_abs``."""

    n_experts: int
    top_k: int
    width: int
    held: Tuple[int, int]
    capacity_factor: float = 0.0  # balanced shares every step sweeps; 0: as long as the data
    dtype: Any = jnp.float32
    # the type of the held experts' grouped products' operands, ``dtype`` where
    # None: train.supcon.build sets bfloat16 beside a float32 ``dtype`` on a
    # TPU, where default precision rounds the same operands inside every call
    product_dtype: Any = None
    router: str = "softmax"  # or "sigmoid": ``route``'s rule
    gate_scale: float = 1.0
    gate_eps: float = 1e-20  # the sigmoid gates' denominator adds it
    bias_rate: float = 0.0  # the step of ``route_bias`` in train mode
    sequence_balance: bool = False  # the balance term row by row
    shared_width: int = 0
    # the shared experts' output scaled by sigmoid(b @ shared_expert_gate)
    shared_expert_gate: bool = False
    rms_eps: float = RMS_EPS

    @nn.compact
    def __call__(self, h: jax.Array, train: bool = False) -> tuple:
        D = h.shape[-1]
        first, count = self.held
        if not (0 <= first and first + count <= self.n_experts and count > 0):
            raise ValueError(f"held {self.held} is no range of {self.n_experts} experts")
        w = {name: self.param(name, normal_init, shape) for name, shape in (
            ("router", (D, self.n_experts)), ("w_gate", (count, D, self.width)),
            ("w_up", (count, D, self.width)), ("w_down", (count, self.width, D)))}
        if self.shared_width:
            w.update({name: self.param(name, normal_init, shape) for name, shape in (
                ("shared_gate", (D, self.shared_width)), ("shared_up", (D, self.shared_width)),
                ("shared_down", (self.shared_width, D)))})
            if self.shared_expert_gate:
                w["shared_expert_gate"] = self.param("shared_expert_gate", normal_init, (D, 1))
        w["norm"] = self.param("norm", nn.initializers.ones, (D,))
        w, h = tie_gradients((w, h))
        b = rms_norm(h, w["norm"], self.rms_eps).reshape(-1, D)
        # float32 at highest: a rounded operand must not flip a choice
        logits = jnp.dot(b.astype(jnp.float32), w["router"], precision=HIGHEST)
        bias = None
        if self.router != "softmax":
            bias = self.variable("batch_stats", "route_bias", jnp.zeros,
                                 (self.n_experts,), jnp.float32)
        probs, top_e, gates = route(logits, self.top_k, self.router,
                                    None if bias is None else bias.value, self.gate_scale,
                                    self.gate_eps)
        load, prob = routing_statistics(probs, top_e)
        provisioned = provisioned_rows(top_e.size, count, self.n_experts, self.capacity_factor)
        y, n_held = held_mix(
            b.astype(self.dtype), top_e, gates,
            *(w[name].astype(self.dtype) for name in ("w_gate", "w_up", "w_down")), first=first,
            chunk=balanced_chunk_rows(top_e.size, count, self.n_experts, provisioned, D,
                                      self.width, self.dtype),
            provisioned=provisioned, product=self.product_dtype)
        if self.shared_width:
            with jax.named_scope(SCOPE_SHARED):
                shared = gated_mlp(b.astype(self.dtype), *(
                    w[name].astype(self.dtype)
                    for name in ("shared_gate", "shared_up", "shared_down")))
                if self.shared_expert_gate:
                    shared = shared * jax.nn.sigmoid(
                        b.astype(self.dtype) @ w["shared_expert_gate"].astype(self.dtype))
                y = y + shared
        out = h + y.reshape(h.shape).astype(h.dtype)
        stats = {"load": load, "prob": prob,
                 "balance": (sequence_balance(probs, top_e, h.shape[0]) if self.sequence_balance
                             else self.n_experts * jnp.sum(load * prob)),
                 "held_share": n_held.astype(jnp.float32) / top_e.size}
        if bias is not None:
            if train and not self.is_initializing():
                bias.value = bias.value + self.bias_rate * jnp.sign(1.0 / self.n_experts - load)
            stats["bias_max_abs"] = jnp.max(jnp.abs(bias.value))
        return out, stats


class DenseLayer(nn.Module):
    """A feed-forward layer without experts, with its pre-norm and its
    residual: ``h + f(rms(h))`` over tokens ``h [R, T, D]``, ``f`` of
    ``width``. A few rows at a time, recomputed in the backward pass
    (``map_row_groups``): the ``[tokens, width]`` intermediates are a
    group's (1.5 GB each over 8 rows of 4,096 tokens at width 11,264)."""

    width: int
    rms_eps: float = RMS_EPS
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h: jax.Array) -> jax.Array:
        D = h.shape[-1]
        w = {name: self.param(name, normal_init, shape) for name, shape in (
            ("w_gate", (D, self.width)), ("w_up", (D, self.width)), ("w_down", (self.width, D)))}
        w["norm"] = self.param("norm", nn.initializers.ones, (D,))
        w, h = tie_gradients((w, h))

        def some_rows(h):
            b = rms_norm(h, w["norm"], self.rms_eps).astype(self.dtype)
            return h + gated_mlp(b, *(w[name].astype(self.dtype)
                                      for name in ("w_gate", "w_up", "w_down"))).astype(h.dtype)

        return map_row_groups(some_rows, h).reshape(h.shape)
