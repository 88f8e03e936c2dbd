"""Grouped-query attention behind a learned key selection.

Every query attends, causally, to the ``topk`` keys that a small indexer
scores highest for it (and to all of its keys while it has no more than
``topk``). The layer's equations, per row of ``T`` tokens, with ``a`` the
pre-normed input:

- ``q, k, v = a W_q, a W_k, a W_v`` in ``H`` query and ``G`` key-value heads
  of ``head_dim``; ``q`` and ``k`` RMS-normed over ``head_dim`` and turned by
  the rotary embedding (rotate-half pairs ``(d, d + head_dim/2)``; the slots
  are cut into contiguous ``mrope_section`` chunks that turn by the token's
  ``(t, i, j)`` position: 0, its patch row, its patch column);
- indexer, on ``stop_gradient(a)``: ``I[t, s] = (J * d_I)^-1/2 * sum_j
  w[t, j] relu(qI[t, j] . kI[s])`` over its ``J`` heads of ``d_I``, float32
  at ``highest`` precision, so that a selection does not flip on operand
  rounding; ``S_t`` = the ``topk`` keys ``s <= t`` of largest ``I[t, s]``,
  ties to the lower ``s``;
- ``o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, h // (H/G)] /
  sqrt(head_dim)) v[s, h // (H/G)]``, then ``W_o``;
- the indexer's own loss: ``KL(pi_t || softmax_{s in S_t} I[t, s])`` summed
  over ``t``, ``pi_t`` the attention probabilities summed over the heads and
  L1-normalised, under ``stop_gradient``: it reaches the indexer's three
  matrices and nothing else.

The computation goes a chunk of ``q_chunk`` queries at a time, each against
the keys at or before its last query, under ``jax.checkpoint`` and one row at
a time (``lax.map``): a ``[heads, q_chunk, keys]`` score block lives, never
``[T, T]``. The selection is a mask over the score block: the ``topk``-th
largest score of a query is found by bisection on the scores' bits (32 counts
over the block; no sort, no gather), and ties at that threshold are cut by a
second bisection on the key's index, which runs only where a block has one.

Two paths from the selection to the output. ``sparse_attention`` is plain
jnp: every ``[heads, q_chunk, keys]`` block of logits and probabilities is an
XLA tensor. ``sparse_attention_kernel`` hands whole rows to the kernel pair
of ``ops/sparse_attention.py``, which keeps a block in VMEM. The layer takes
the second where its owner says the program is one TPU's (``kernel``) and
its own dtype and shape allow it (``SparseAttention.kernel_reason``); off
the TPU XLA's products are exact, and routing there would only make CPU runs
less precise.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from simclr_pytorch_distributed_tpu.ops import sparse_attention as kernel_ops

SCOPE_INDEXER = "indexer"
# rows of a batch that the attention layer takes at a time (SparseAttention)
ROW_GROUP = 2
RMS_EPS = 1e-6
HIGHEST = lax.Precision.HIGHEST
normal_init = nn.initializers.normal(0.02)


@jax.custom_vjp
def tie_gradients(tree):
    """The identity, with the cotangents of ``tree``'s leaves tied together
    (``lax.optimization_barrier``) in the backward pass. A layer passes its
    weights and its input through it: the weight gradients then have to be
    there before the input's gradient goes on to the layer before. Without
    it XLA fuses each weight-gradient product into the optimizer's update of
    that weight and runs them all after the last layer's backward, keeping
    every layer's activations and cotangents (1.9 GB a layer at the
    benchmark's size) until then."""
    return tree


tie_gradients.defvjp(lambda tree: (tree, None),
                     lambda _, ct: (lax.optimization_barrier(ct),))


def map_row_groups(fn, h: jax.Array):
    """``fn`` over ``h [R, ...]`` a group of ``ROW_GROUP`` rows at a time
    (``lax.map``), each group recomputed in the backward pass
    (``jax.checkpoint``): a layer's working set is then a group's. Returns
    ``fn``'s results stacked, ``[R / group, ...]`` a leaf."""
    group = math.gcd(h.shape[0], ROW_GROUP)
    return lax.map(jax.checkpoint(fn), h.reshape(h.shape[0] // group, group, *h.shape[1:]))


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = RMS_EPS) -> jax.Array:
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, in float32,
    returned in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope_tables(grid: int, head_dim: int, theta: float, sections: Sequence[int]):
    """``(cos, sin)``, each ``[grid * grid, head_dim]`` float32, for patch
    tokens in raster order: slot ``m`` of the ``head_dim / 2`` turns by
    ``pos[m] * theta ** (-m / (head_dim / 2))`` where ``pos`` is 0 in the
    first section, the patch's row in the second and its column in the
    third; the two halves of a head share the table (rotate-half)."""
    half = head_dim // 2
    if sum(sections) != half or len(sections) != 3:
        raise ValueError(f"mrope_section {tuple(sections)} does not cut {half} slots in three")
    idx = jnp.arange(grid * grid)
    pos = jnp.stack([jnp.zeros_like(idx), idx // grid, idx % grid]).astype(jnp.float32)
    which = jnp.repeat(jnp.arange(3), jnp.asarray(sections), total_repeat_length=half)
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos[which].T * inv_freq  # [T, half]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``x`` is ``[..., T, heads, head_dim]``; pairs ``(d, d + head_dim/2)``."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    cos, sin = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    return x * cos + turned * sin


def _sortable(x: jax.Array) -> jax.Array:
    """float32 -> uint32 with the same order (and every key above 0)."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def select_topk(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """Boolean mask ``[..., n]``: of each row's ``valid`` entries the ``k`` of
    largest ``scores`` (all of them where there are no more than ``k``), ties
    to the lower index: what ``lax.top_k`` picks, as a mask and with no sort.

    The ``k``-th largest key is the largest ``v`` with ``count(keys >= v) >=
    k``: its bits are fixed from the top down, one count over the row a bit.
    """
    n = scores.shape[-1]
    keys = jnp.where(valid, _sortable(scores), jnp.uint32(0))

    def fix_bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        count = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, prefix)

    kth = lax.fori_loop(0, 32, fix_bit, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    mask = (keys >= kth) & valid

    def cut_ties(_):
        above = (keys > kth) & valid
        tie = (keys == kth) & valid
        need = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
        idx = jnp.arange(n, dtype=jnp.int32)
        bits = max(1, (n - 1).bit_length())

        # the largest x with fewer than ``need`` ties below index x: the
        # last tie that is taken
        def fix_index_bit(i, x):
            cand = x | (jnp.int32(1) << (bits - 1 - i))
            below = jnp.sum(tie & (idx < cand[..., None]), axis=-1, dtype=jnp.int32)
            return jnp.where(below < need, cand, x)

        last = lax.fori_loop(0, bits, fix_index_bit, jnp.zeros(scores.shape[:-1], jnp.int32))
        return above | (tie & (idx <= last[..., None]))

    tied = jnp.any(jnp.sum(mask, axis=-1, dtype=jnp.int32) > k)
    return lax.cond(tied, cut_ties, lambda _: mask, None)


def index_scores(qi: jax.Array, ki: jax.Array, wi: jax.Array) -> jax.Array:
    """``[Q, J, d], [S, d], [Q, J] -> [Q, S]`` float32 at ``highest``."""
    dots = jnp.einsum("qjd,sd->jqs", qi.astype(jnp.float32), ki.astype(jnp.float32),
                      precision=HIGHEST)
    weighted = jnp.einsum("jqs,qj->qs", jax.nn.relu(dots), wi.astype(jnp.float32),
                          precision=HIGHEST)
    return weighted / math.sqrt(qi.shape[1] * qi.shape[2])


def _select(qi, ki, wi, first: int, topk: int):
    """The indexer's ``(scores, chosen)``, both ``[Q, S]``, for one row's
    queries ``first .. first + Q - 1`` against its keys ``0 .. S - 1``: of a
    query's keys at or before it, the ``topk`` it scores highest."""
    Q, S = qi.shape[0], ki.shape[0]
    causal = jnp.arange(S)[None, :] <= first + jnp.arange(Q)[:, None]
    with jax.named_scope(SCOPE_INDEXER):
        scores = index_scores(qi, ki, wi)
        # with no more keys than topk every causal key is selected
        chosen = causal if S <= topk else select_topk(scores, causal, topk)
    return scores, chosen


def _index_kl(scores, chosen, target):
    """``KL(target || softmax over the chosen keys of scores)`` summed over
    the queries; ``target [Q, S]`` is the attention probabilities averaged
    over the heads (rows sum to 1) and carries no gradient."""
    with jax.named_scope(SCOPE_INDEXER):
        target = lax.stop_gradient(target)
        log_index = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
        log_target = jnp.log(jnp.where(target > 0, target, 1.0))
        return jnp.sum(jnp.where(target > 0, target * (log_target - log_index), 0.0))


def _attend_selected(q, k, v, chosen):
    """``(o [Q, H * d], target [Q, S])`` of ``q [Q, H, d]`` against ``k``/``v``
    ``[S, G, d]`` over the ``chosen [Q, S]`` keys: the heads' outputs and the
    attention probabilities averaged over the heads. Plain jnp: what
    ops/sparse_attention.py's kernel pair computes, and its tests' oracle."""
    Q, H, d = q.shape
    G = k.shape[1]
    logits = jnp.einsum("qghd,sgd->ghqs", q.reshape(Q, G, H // G, d), k) / math.sqrt(d)
    logits = jnp.where(chosen, logits.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("ghqs,sgd->qghd", probs.astype(v.dtype), v).reshape(Q, H * d)
    with jax.named_scope(SCOPE_INDEXER):
        target = jnp.sum(probs, axis=(0, 1)) / H  # rows sum to 1
    return o, target


def _attend_chunk(q, k, v, qi, ki, wi, first: int, topk: int):
    """One row's queries ``first .. first + Q - 1`` against its keys ``0 ..
    first + Q - 1``: ``q [Q, H, d]``, ``k``/``v`` ``[S, G, d]``, indexer
    ``qi [Q, J, dI]``, ``ki [S, dI]``, ``wi [Q, J]``. Returns ``(o [Q, H *
    d], sum over the queries of the indexer's KL)``. XLA's path."""
    scores, chosen = _select(qi, ki, wi, first, topk)
    o, target = _attend_selected(q, k, v, chosen)
    return o, _index_kl(scores, chosen, target)


def _chunks(T: int, q_chunk: int):
    """``(first, last)`` of each chunk of queries: ``q_chunk`` at a time, the
    whole row where that does not cut it."""
    if T % q_chunk:
        q_chunk = T
    return [(first, first + q_chunk) for first in range(0, T, q_chunk)]


def sparse_attention(q, k, v, qi, ki, wi, *, topk: int, q_chunk: int):
    """All rows: ``q [R, T, H, d]``, ``k``/``v`` ``[R, T, G, d]``, ``qi [R, T,
    J, dI]``, ``ki [R, T, dI]``, ``wi [R, T, J]`` -> ``(o [R, T, H * d],
    KL summed over rows and queries)``."""
    outs, kl = [], jnp.zeros((), jnp.float32)
    for first, last in _chunks(q.shape[1], q_chunk):
        one_row = jax.checkpoint(
            lambda row, first=first: _attend_chunk(*row, first=first, topk=topk))
        o, kls = lax.map(one_row, (q[:, first:last], k[:, :last], v[:, :last],
                                   qi[:, first:last], ki[:, :last], wi[:, first:last]))
        outs.append(o)
        kl = kl + jnp.sum(kls)
    return jnp.concatenate(outs, axis=1), kl


def sparse_attention_kernel(q, k, v, qi, ki, wi, *, topk: int, q_chunk: int,
                            interpret: bool = False):
    """``sparse_attention`` with everything between the selection and the
    output in ``ops/sparse_attention.py``'s kernel pair, whole rows a call:
    the indexer scores and selects a chunk of queries at a time as above
    (recomputed in the backward pass: its ``[J, Q, S]`` products are the only
    score-sized tensors left), the chunks' masks are laid side by side into
    one ``[R, T, T]`` int8 mask, and the KL takes the kernel's head-averaged
    probabilities chunk by chunk. The two paths share ``_select`` and
    ``_index_kl`` and nothing else."""
    R, T, H, d = q.shape
    chunks = _chunks(T, q_chunk)
    selected = []
    for first, last in chunks:
        one_row = jax.checkpoint(
            lambda row, first=first: _select(*row, first=first, topk=topk))
        selected.append(lax.map(one_row, (qi[:, first:last], ki[:, :last], wi[:, first:last])))
    mask = jnp.concatenate(
        [jnp.pad(chosen.astype(jnp.int8), ((0, 0), (0, 0), (0, T - last)))
         for (_, last), (_, chosen) in zip(chunks, selected)], axis=1)
    # the kernels take queries minor: [R, H*d, T] and [R, keys, queries]
    o_t, target_t = kernel_ops.attend(
        q.transpose(0, 2, 3, 1).reshape(R, H * d, T), k.reshape(R, T, -1),
        v.reshape(R, T, -1), mask.swapaxes(1, 2), n_heads=H, interpret=interpret)
    kl = jnp.zeros((), jnp.float32)
    for (first, last), (scores, chosen) in zip(chunks, selected):
        kl = kl + _index_kl(scores, chosen, target_t[:, :last, first:last].swapaxes(1, 2))
    return o_t.swapaxes(1, 2), kl


def _interpret_kernel() -> bool:
    """The kernel pair runs compiled on a TPU; anywhere else only its tests
    call it, interpreted."""
    return jax.default_backend() != "tpu"


class SparseAttention(nn.Module):
    """The layer with its pre-norm and its residual, over tokens ``h [R, T,
    D]`` laid out as a ``grid x grid`` raster: returns ``(h + W_o attention(
    rms(h)), the indexer's KL averaged over rows and tokens)``. No bias."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    topk: int
    q_chunk: int
    rope_theta: float
    mrope_section: Sequence[int]
    dtype: Any = jnp.float32
    # scores, mask, softmax and values through ops/sparse_attention.py's
    # kernel pair. Set by the owner that knows the mesh holds ONE device and
    # the backend is a TPU (train.supcon.build); the dtype and the row's
    # shape can still say no (kernel_reason).
    kernel: bool = False

    def kernel_reason(self, tokens: int) -> Optional[str]:
        """Why rows of ``tokens`` keep XLA's path through this layer, or
        None: the kernel pair is float32 in and out, at a shape it tiles
        within its VMEM budget. ``__call__`` and ``attention_plan`` both ask
        here."""
        if self.dtype != jnp.float32:
            return f"compute dtype {jnp.dtype(self.dtype).name}"
        return kernel_ops.unsupported(tokens, self.n_heads, self.n_kv_heads, self.head_dim)

    @nn.compact
    def __call__(self, h: jax.Array) -> tuple:
        R, T, D = h.shape
        grid = math.isqrt(T)
        H, G, d, J, dI = (self.n_heads, self.n_kv_heads, self.head_dim,
                          self.index_heads, self.index_dim)

        w = {name: self.param(name, normal_init, (D, cols)) for name, cols in (
            ("q", H * d), ("k", G * d), ("v", G * d), ("index_q", J * dI), ("index_k", dI),
            ("index_w", J))}
        w["o"] = self.param("o", normal_init, (H * d, D))
        w.update({name: self.param(name, nn.initializers.ones, (n,))
                  for name, n in (("norm", D), ("q_norm", d), ("k_norm", d))})
        w, h = tie_gradients((w, h))
        w = {name: x.astype(self.dtype) for name, x in w.items()}
        cos, sin = rope_tables(grid, d, self.rope_theta, self.mrope_section)
        attend = sparse_attention
        if self.kernel and self.kernel_reason(T) is None:
            attend = functools.partial(sparse_attention_kernel, interpret=_interpret_kernel())

        def some_rows(h):
            n = h.shape[0]
            a = rms_norm(h, w["norm"]).astype(self.dtype)
            q = (a @ w["q"]).reshape(n, T, H, d)
            k = (a @ w["k"]).reshape(n, T, G, d)
            v = (a @ w["v"]).reshape(n, T, G, d)
            q = apply_rope(rms_norm(q, w["q_norm"]), cos, sin)
            k = apply_rope(rms_norm(k, w["k_norm"]), cos, sin)
            with jax.named_scope(SCOPE_INDEXER):
                held = lax.stop_gradient(a)
                qi = (held @ w["index_q"]).reshape(n, T, J, dI)
                ki = held @ w["index_k"]
                wi = held @ w["index_w"]
            o, kl = attend(q, k, v, qi, ki, wi, topk=self.topk, q_chunk=self.q_chunk)
            return h + (o @ w["o"]).astype(h.dtype), kl

        # a few rows at a time, recomputed in the backward pass: the layer's
        # working set (q, its rotation, the heads' outputs and their
        # cotangents, 0.5 GB each over 8 rows of 4,096 tokens) is a group's
        out, kl = map_row_groups(some_rows, h)
        return out.reshape(R, T, D), jnp.sum(kl) / (R * T)
