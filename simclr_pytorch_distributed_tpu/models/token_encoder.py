"""Encoders over patch tokens: a language model's block stack (sparse
attention, routed experts) as an image encoder.

``[N, H, W, 3]`` views are cut into non-overlapping ``patch x patch`` patches
in raster order, embedded linearly, run through ``layers`` pre-norm blocks
(``models/sparse_attention.py``, then ``models/experts.py``), RMS-normed and
averaged over the tokens: ``[N, hidden]`` float32 features, what
``SupConResNet`` hands its projection head. The widths of each preset live
in ``TOKEN_ENCODERS`` and nowhere else; ``models/resnet.MODEL_DICT`` gets one
entry a preset.

Beside the features the encoder keeps, a layer, two running statistics in
``batch_stats`` (``prob_mean``, ``load_mean`` over all experts, updated in
train mode with ``STATS_MOMENTUM``) and sows into the collection ``aux``
what the train step adds to its loss (``aux_loss``: the sum over the layers
of ``balance_coef * balance + index_coef * indexer's KL``) and what it
writes to the metric ring (``TokenEncoder.aux_metric_keys``, each the
layers' mean; ``TokenEncoder.read_aux`` takes both out again).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from simclr_pytorch_distributed_tpu.models.experts import ExpertLayer
from simclr_pytorch_distributed_tpu.models.resnet import MODEL_DICT, build_encoder
from simclr_pytorch_distributed_tpu.models.sparse_attention import (
    SparseAttention,
    normal_init,
    rms_norm,
)

AUX_COLLECTION = "aux"
AUX_METRIC_KEYS = ("indexer_kl", "moe_held_share", "moe_load_max_over_mean")
# weight of the new batch in the running statistics: BatchNorm's, which the
# ResNets' statistics move with (models/norm.py)
STATS_MOMENTUM = 0.1


@dataclasses.dataclass(frozen=True)
class TokenEncoderSpec:
    patch: int
    hidden: int
    layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    topk: int
    q_chunk: int
    rope_theta: float
    mrope_section: Tuple[int, int, int]
    n_experts: int
    top_k: int
    expert_width: int
    held: Tuple[int, int]  # (first, count) of n_experts
    # balanced shares of assignments an expert layer sweeps every step,
    # whatever the routing (models/experts.py): twice the balanced load, the
    # capacity factor of GShard's training runs, and beyond it as the data asks
    capacity_factor: float = 2.0
    balance_coef: float = 0.001
    index_coef: float = 1.0


TOKEN_ENCODERS = {
    # Keye-VL-2.0-30B-A3B's language-model block at its published widths
    # (https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json),
    # one chip's share of an eight-way expert split: 16 of the 128 experts,
    # 5 of the 48 layers (benchmark/configs/keye-vl2-a3b-ep8.json has the cut)
    "keye-vl2-a3b-ep8": TokenEncoderSpec(
        patch=16, hidden=2048, layers=5, n_heads=32, n_kv_heads=4, head_dim=128,
        index_heads=16, index_dim=64, topk=2048, q_chunk=512, rope_theta=1e7,
        mrope_section=(16, 24, 24), n_experts=128, top_k=8, expert_width=768, held=(0, 16)),
    # the same block at test size: 16 tokens at 16x16 pixels, the selection
    # bites from the seventh token on, half of the experts held
    "keye-vl2-tiny": TokenEncoderSpec(
        patch=4, hidden=32, layers=2, n_heads=4, n_kv_heads=2, head_dim=8,
        index_heads=2, index_dim=4, topk=6, q_chunk=4, rope_theta=1e7,
        mrope_section=(1, 1, 2), n_experts=8, top_k=2, expert_width=16, held=(0, 4)),
}


def attention_attrs(s: TokenEncoderSpec, dtype, kernel: bool) -> dict:
    """The attributes of a block's ``SparseAttention``: what ``Block`` builds
    its layer from and ``attention_plan`` asks the same layer with."""
    return dict(
        n_heads=s.n_heads, n_kv_heads=s.n_kv_heads, head_dim=s.head_dim,
        index_heads=s.index_heads, index_dim=s.index_dim, topk=s.topk,
        q_chunk=s.q_chunk, rope_theta=s.rope_theta, mrope_section=s.mrope_section,
        dtype=dtype, kernel=kernel)


class Block(nn.Module):
    spec: TokenEncoderSpec
    dtype: Any = jnp.float32
    remat: bool = False
    attn_kernel: bool = False

    @nn.compact
    def __call__(self, h: jax.Array, train: bool):
        s = self.spec
        wrap = nn.remat if self.remat else (lambda cls: cls)
        h, kl = wrap(SparseAttention)(
            **attention_attrs(s, self.dtype, self.attn_kernel), name="attn")(h)
        h, routed = wrap(ExpertLayer)(
            n_experts=s.n_experts, top_k=s.top_k, width=s.expert_width, held=s.held,
            capacity_factor=s.capacity_factor, dtype=self.dtype, name="moe")(h)
        for name in ("prob", "load"):  # the forward pass's order
            mean = self.variable("batch_stats", f"{name}_mean", jnp.zeros,
                                 (s.n_experts,), jnp.float32)
            if train and not self.is_initializing():
                mean.value = ((1.0 - STATS_MOMENTUM) * mean.value
                              + STATS_MOMENTUM * jax.lax.stop_gradient(routed[name]))
        return h, kl, routed


class TokenEncoder(nn.Module):
    """``[N, H, W, 3] -> [N, spec.hidden]`` float32; see the module docstring."""

    spec: Optional[TokenEncoderSpec] = None
    dtype: Any = jnp.float32
    remat: bool = False  # each block's attention and expert layer recomputed in the backward
    # attention through ops/sparse_attention.py's kernel pair: set by
    # train.supcon.build on a one-device TPU mesh; each layer's dtype and
    # shape can still say no (SparseAttention.kernel_reason)
    attn_kernel: bool = False
    # the ring columns this encoder sows beside its ``aux_loss``; an encoder
    # without the attribute (a ResNet) sows nothing
    aux_metric_keys = AUX_METRIC_KEYS

    @staticmethod
    def read_aux(sown: dict):
        """``(aux_loss, {ring column: value})`` from what a train-mode
        ``apply`` sowed into the collection ``aux`` under this module."""
        return sown["aux_loss"], {k: sown[k] for k in AUX_METRIC_KEYS}

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        s = self.spec
        n, height, width, c = x.shape
        p = s.patch
        if height % p or width % p:
            raise ValueError(f"{height}x{width} views do not cut into {p}x{p} patches")
        u = x.astype(self.dtype).reshape(n, height // p, p, width // p, p, c)
        u = u.transpose(0, 1, 3, 2, 4, 5).reshape(n, (height // p) * (width // p), p * p * c)
        h = nn.Dense(s.hidden, kernel_init=normal_init, dtype=self.dtype, name="patch_embed")(u)
        aux_loss, sums = jnp.zeros((), jnp.float32), dict.fromkeys(AUX_METRIC_KEYS, 0.0)
        for k in range(s.layers):
            h, kl, routed = Block(s, self.dtype, self.remat, self.attn_kernel,
                                  name=f"block{k}")(h, train)
            aux_loss = aux_loss + s.balance_coef * routed["balance"] + s.index_coef * kl
            sums["indexer_kl"] += kl
            sums["moe_held_share"] += routed["held_share"]
            sums["moe_load_max_over_mean"] += jnp.max(routed["load"]) * s.n_experts
        z = rms_norm(h, self.param("final_norm", nn.initializers.ones, (s.hidden,)))
        keep_last = lambda _, value: value  # noqa: E731
        self.sow(AUX_COLLECTION, "aux_loss", aux_loss, reduce_fn=keep_last, init_fn=lambda: None)
        for key, total in sums.items():
            self.sow(AUX_COLLECTION, key, jax.lax.stop_gradient(total / s.layers),
                     reduce_fn=keep_last, init_fn=lambda: None)
        return jnp.mean(z.astype(jnp.float32), axis=1)


def attention_plan(
    model: str, size: int, owner_reason: Optional[str] = None, **encoder_kwargs
) -> list:
    """One ``{"name", "reason"}`` per attention layer of ``model``: ``reason``
    is None where the layer runs ops/sparse_attention.py's kernel pair over
    the patch tokens of ``size x size`` views and otherwise says why it stays
    XLA's: the owner's
    (``owner_reason``: mesh size, backend) or the layer's own
    ``SparseAttention.kernel_reason``, which is what its ``__call__`` asks
    too. An encoder that is no ``TokenEncoder`` has no such layer."""
    mod = build_encoder(model, **encoder_kwargs)
    if not isinstance(mod, TokenEncoder):
        return []
    layer = SparseAttention(**attention_attrs(mod.spec, mod.dtype, True))
    reason = owner_reason or layer.kernel_reason((size // mod.spec.patch) ** 2)
    return [{"name": f"block{k}", "reason": reason} for k in range(mod.spec.layers)]


def match_tree(encoder_params: dict) -> Optional[str]:
    """The preset whose parameter tree ``encoder_params`` is, or None."""
    if "patch_embed" not in encoder_params:
        return None
    layers = sum(1 for name in encoder_params if name.startswith("block"))
    moe = encoder_params["block0"]["moe"]
    shape = (layers, *moe["router"].shape, *moe["w_gate"].shape)
    for name, s in TOKEN_ENCODERS.items():
        if shape == (s.layers, s.hidden, s.n_experts, s.held[1], s.hidden, s.expert_width):
            return name
    return None


MODEL_DICT.update({
    name: (functools.partial(TokenEncoder, spec=spec), spec.hidden)
    for name, spec in TOKEN_ENCODERS.items()
})
