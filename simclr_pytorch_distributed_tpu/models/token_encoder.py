"""Encoders over patch tokens: a language model's block stack as an image
encoder. Four blocks so far, each a preset's data:

- Keye-VL-2.0's: grouped-query attention behind a learned top-k key indexer
  (``models/sparse_attention.py``), then softmax-routed experts
  (``models/experts.py``), in every layer;
- Moonlight-16B-A3B's (``deepseek_v3``): multi-head latent attention with one
  shared rotary key (``models/latent_attention.py``), then a dense
  feed-forward layer in the leading ``dense_layers`` blocks and, in the rest,
  sigmoid-routed experts under a load-correcting bias beside shared experts;
- Qwen3-Next-80B-A3B's (``qwen3_next``): three Gated DeltaNet layers
  (``models/gated_delta.py``) to one gated full-attention layer
  (``models/gated_attention.py``), every layer then softmax-routed experts
  beside a shared expert that a sigmoid gates;
- LFM2-8B-A1B's (``lfm2_moe``): gated short convolutions
  (``models/gated_conv.py``) and grouped-query attention without an output
  gate, in the order the preset lists (``layer_kinds``), then a dense
  feed-forward layer in the leading ``dense_layers`` blocks and, in the rest,
  sigmoid-routed experts under a load-correcting bias with no shared expert.

``[N, H, W, 3]`` views are cut into non-overlapping ``patch x patch`` patches
in raster order, embedded linearly, run through ``layers`` pre-norm blocks,
RMS-normed and averaged over the tokens: ``[N, hidden]`` float32 features,
what ``SupConResNet`` hands its projection head. What a layer is made of
(each layer's kind of mixer and its widths, how many leading layers are
dense and how wide, the router's rule, the shared experts' width and gate,
the gates' scale) lives in ``TOKEN_ENCODERS`` and nowhere else; ``models/resnet.MODEL_DICT`` gets
one entry a preset. Module names are ``block<k>/attn`` and ``block<k>/moe``
(``block<k>/mlp`` in a dense layer).

Beside the features the encoder keeps, an expert layer, running statistics
in ``batch_stats`` (``prob_mean``, ``load_mean`` over all experts, updated in
train mode with ``STATS_MOMENTUM``; under ``moe``, where the router has one,
its bias ``route_bias``) and sows into the collection ``aux`` what the train
step adds to its loss (``aux_loss``: the sum over the layers of
``balance_coef * balance + index_coef * indexer's KL``) and what it writes to
the metric ring (``ring_columns`` of the preset, each the mean over the
layers that have it: the indexer's KL, the routing's shares and, over the
Gated DeltaNet layers, ``delta_decay_mean``, the mean of their per-token
decays ``exp(g)``; ``TokenEncoder.read_aux`` takes both out again).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from simclr_pytorch_distributed_tpu.models.experts import DenseLayer, ExpertLayer
from simclr_pytorch_distributed_tpu.models.gated_attention import GatedAttention
from simclr_pytorch_distributed_tpu.models.gated_conv import GatedShortConv
from simclr_pytorch_distributed_tpu.models.gated_delta import GatedDeltaNet
from simclr_pytorch_distributed_tpu.models.latent_attention import LatentAttention
from simclr_pytorch_distributed_tpu.models.resnet import MODEL_DICT, build_encoder
from simclr_pytorch_distributed_tpu.models.sparse_attention import (
    RMS_EPS,
    SparseAttention,
    normal_init,
    rms_norm,
)

AUX_COLLECTION = "aux"
# the ring columns of a block with sparse attention and a softmax router
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
    q_chunk: int
    rope_theta: float
    n_experts: int
    top_k: int
    expert_width: int
    held: Tuple[int, int]  # (first, count) of n_experts
    # "sparse": SparseAttention, which takes the next six; "latent":
    # LatentAttention, which takes the four after them; "gated":
    # GatedAttention, which takes n_kv_heads, head_dim and rope_dim (the
    # rotary dimensions of a head); "full": GatedAttention without its gate
    attention: str = "sparse"
    # every full_attention_interval-th layer has ``attention`` and the others
    # are Gated DeltaNet ("linear"), which takes the next six; 0: none is
    full_attention_interval: int = 0
    # each layer's kind where the preset lists them, and then neither of the
    # two above decides; "conv" is GatedShortConv, which takes conv_width
    layer_kinds: Tuple[str, ...] = ()
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    conv_width: int = 0
    delta_chunk: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    index_heads: int = 0
    index_dim: int = 0
    topk: int = 0
    mrope_section: Tuple[int, ...] = ()
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    # leading layers with a dense feed-forward layer in the experts' place
    dense_layers: int = 0
    dense_width: int = 0
    # experts.route's rule, and what the "sigmoid" rule takes
    router: str = "softmax"
    gate_scale: float = 1.0
    gate_eps: float = 1e-20
    bias_rate: float = 0.0
    sequence_balance: bool = False
    shared_width: int = 0
    shared_expert_gate: bool = False
    rms_eps: float = RMS_EPS
    # balanced shares of assignments an expert layer sweeps every step,
    # whatever the routing (models/experts.py): twice the balanced load, the
    # capacity factor of GShard's training runs, and beyond it as the data asks
    capacity_factor: float = 2.0
    # the balance term's weight in the loss; 0: no such term
    balance_coef: float = 0.001
    index_coef: float = 1.0

    def attention_of(self, index: int) -> str:
        """The kind of layer ``index``'s mixer."""
        if self.layer_kinds:
            return self.layer_kinds[index]
        if self.full_attention_interval and (index + 1) % self.full_attention_interval:
            return "linear"
        return self.attention

    @property
    def ring_columns(self) -> Tuple[str, ...]:
        """What an encoder of this preset sows for the metric ring."""
        kinds = {self.attention_of(k) for k in range(self.layers)}
        return ((("indexer_kl",) if "sparse" in kinds else ())
                + ("moe_held_share", "moe_load_max_over_mean")
                + (("route_bias_max_abs",) if self.router != "softmax" else ())
                + (("delta_decay_mean",) if "linear" in kinds else ()))


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
    # Moonlight-16B-A3B's block (model_type deepseek_v3) at its published widths
    # (https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json),
    # one chip's share of an eight-way expert split: 8 of the 64 routed
    # experts, the shared experts whole, the leading dense layer and 4 of the
    # 26 that follow (benchmark/configs/moonlight-16b-a3b-ep8.json has the cut);
    # bias_rate and the balance term are DeepSeek-V3's, whose method the
    # config names
    "moonlight-16b-a3b-ep8": TokenEncoderSpec(
        patch=16, hidden=2048, layers=5, n_heads=16, q_chunk=512, rope_theta=5e4,
        attention="latent", kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
        dense_layers=1, dense_width=11264, n_experts=64, top_k=6, expert_width=1408,
        held=(0, 8), router="sigmoid", gate_scale=2.446, bias_rate=0.001,
        sequence_balance=True, shared_width=2816, rms_eps=1e-5, balance_coef=1e-4),
    # the same block at test size: one dense layer and one of experts, 16
    # tokens, query/key heads of 12 beside value heads of 8, half of the
    # experts held
    "moonlight-tiny": TokenEncoderSpec(
        patch=4, hidden=32, layers=2, n_heads=4, q_chunk=4, rope_theta=5e4,
        attention="latent", kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
        dense_layers=1, dense_width=48, n_experts=8, top_k=2, expert_width=16,
        held=(0, 4), router="sigmoid", gate_scale=2.446, bias_rate=0.001,
        sequence_balance=True, shared_width=24, rms_eps=1e-5, balance_coef=1e-4),
    # Qwen3-Next-80B-A3B's block (model_type qwen3_next) at its published
    # widths (https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json),
    # one chip's share of a 32-way expert split: 16 of the 512 experts, one
    # whole period of the layer pattern (three Gated DeltaNet layers, then
    # the full layer) of the 48 (benchmark/configs/qwen3-next-80b-a3b-ep32.json
    # has the cut)
    "qwen3-next-80b-a3b-ep32": TokenEncoderSpec(
        patch=16, hidden=2048, layers=4, n_heads=16, n_kv_heads=2, head_dim=256, rope_dim=64,
        q_chunk=512, rope_theta=1e7, attention="gated", full_attention_interval=4,
        linear_key_heads=16, linear_value_heads=32, linear_key_dim=128, linear_value_dim=128,
        conv_width=4, delta_chunk=64, n_experts=512, top_k=10, expert_width=512,
        held=(0, 16), shared_width=512, shared_expert_gate=True),
    # the same block at test size: one period, 16 tokens in chunks of 4, half
    # of the experts held
    "qwen3-next-tiny": TokenEncoderSpec(
        patch=4, hidden=32, layers=4, n_heads=4, n_kv_heads=2, head_dim=8, rope_dim=2,
        q_chunk=4, rope_theta=1e7, attention="gated", full_attention_interval=4,
        linear_key_heads=2, linear_value_heads=4, linear_key_dim=8, linear_value_dim=8,
        conv_width=4, delta_chunk=4, n_experts=8, top_k=2, expert_width=16, held=(0, 4),
        shared_width=16, shared_expert_gate=True),
    # LFM2-8B-A1B's block (model_type lfm2_moe) at its published widths
    # (https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json), one
    # chip's share of a four-way expert split: 8 of the 32 experts, the first
    # leading dense layer and the period after the two leading ones
    # (published layers 0 and 2-5; benchmark/configs/lfm2-8b-a1b-ep4.json has
    # the cut); the config names no balance term, and the bias's rate is
    # DeepSeek-V3's
    "lfm2-8b-a1b-ep4": TokenEncoderSpec(
        patch=16, hidden=2048, layers=5, n_heads=32, n_kv_heads=8, head_dim=64, rope_dim=64,
        q_chunk=512, rope_theta=1e6, attention="full",
        layer_kinds=("conv", "full", "conv", "conv", "conv"), conv_width=3, dense_layers=1,
        dense_width=7168, n_experts=32, top_k=4, expert_width=1792, held=(0, 8),
        router="sigmoid", gate_eps=1e-6, bias_rate=0.001, rms_eps=1e-5, balance_coef=0.0),
    # the same block at test size: the same five kinds of layer, 16 tokens,
    # half of the experts held
    "lfm2-tiny": TokenEncoderSpec(
        patch=4, hidden=32, layers=5, n_heads=4, n_kv_heads=2, head_dim=8, rope_dim=8,
        q_chunk=4, rope_theta=1e6, attention="full",
        layer_kinds=("conv", "full", "conv", "conv", "conv"), conv_width=3, dense_layers=1,
        dense_width=48, n_experts=8, top_k=2, expert_width=16, held=(0, 4),
        router="sigmoid", gate_eps=1e-6, bias_rate=0.001, rms_eps=1e-5, balance_coef=0.0),
}


def attention_attrs(s: TokenEncoderSpec, dtype, kernel: bool) -> dict:
    """The attributes of a block's ``SparseAttention``: what ``Block`` builds
    its layer from and ``attention_plan`` asks the same layer with."""
    return dict(
        n_heads=s.n_heads, n_kv_heads=s.n_kv_heads, head_dim=s.head_dim,
        index_heads=s.index_heads, index_dim=s.index_dim, topk=s.topk,
        q_chunk=s.q_chunk, rope_theta=s.rope_theta, mrope_section=s.mrope_section,
        dtype=dtype, kernel=kernel)


def latent_attrs(s: TokenEncoderSpec, dtype) -> dict:
    """The attributes of a block's ``LatentAttention``."""
    return dict(
        n_heads=s.n_heads, kv_rank=s.kv_rank, nope_dim=s.nope_dim, rope_dim=s.rope_dim,
        v_dim=s.v_dim, q_chunk=s.q_chunk, rope_theta=s.rope_theta, rms_eps=s.rms_eps,
        dtype=dtype)


def gated_attrs(s: TokenEncoderSpec, dtype) -> dict:
    """The attributes of a block's ``GatedAttention``."""
    return dict(
        n_heads=s.n_heads, n_kv_heads=s.n_kv_heads, head_dim=s.head_dim, rope_dim=s.rope_dim,
        q_chunk=s.q_chunk, rope_theta=s.rope_theta, rms_eps=s.rms_eps, dtype=dtype)


def delta_attrs(s: TokenEncoderSpec, dtype, kernel: bool) -> dict:
    """The attributes of a block's ``GatedDeltaNet``: what ``Block`` builds
    its layer from and ``train.supcon.plan_linear_attention`` asks the same
    layer with."""
    return dict(
        n_key_heads=s.linear_key_heads, n_value_heads=s.linear_value_heads,
        key_dim=s.linear_key_dim, value_dim=s.linear_value_dim, conv_width=s.conv_width,
        chunk=s.delta_chunk, rms_eps=s.rms_eps, dtype=dtype, kernel=kernel)


def expert_attrs(s: TokenEncoderSpec, dtype, product_dtype=None) -> dict:
    """The attributes of a block's ``ExpertLayer``; ``product_dtype`` is the
    type of its grouped products' operands (``dtype`` where None)."""
    return dict(
        n_experts=s.n_experts, top_k=s.top_k, width=s.expert_width, held=s.held,
        capacity_factor=s.capacity_factor, dtype=dtype, product_dtype=product_dtype,
        router=s.router, gate_scale=s.gate_scale, gate_eps=s.gate_eps, bias_rate=s.bias_rate,
        sequence_balance=s.sequence_balance, shared_width=s.shared_width,
        shared_expert_gate=s.shared_expert_gate, rms_eps=s.rms_eps)


class Block(nn.Module):
    """Layer ``index`` of the preset: its mixer, then its dense layer or its
    experts. Returns ``(h, the indexer's KL or None, the Gated DeltaNet's
    mean decay or None, the expert layer's statistics or None)``."""

    spec: TokenEncoderSpec
    dtype: Any = jnp.float32
    remat: bool = False
    attn_kernel: bool = False
    index: int = 0
    expert_product_dtype: Any = None

    @nn.compact
    def __call__(self, h: jax.Array, train: bool):
        s = self.spec
        wrap = nn.remat if self.remat else (lambda cls, **_: cls)
        kl = decay = None
        kind = s.attention_of(self.index)
        if kind == "sparse":
            h, kl = wrap(SparseAttention)(
                **attention_attrs(s, self.dtype, self.attn_kernel), name="attn")(h)
        # the other four recompute their row groups themselves, remat or not
        elif kind == "latent":
            h = LatentAttention(**latent_attrs(s, self.dtype), name="attn")(h)
        elif kind in ("gated", "full"):
            h = GatedAttention(**gated_attrs(s, self.dtype), output_gate=kind == "gated",
                               name="attn")(h)
        elif kind == "linear":
            h, decay = GatedDeltaNet(**delta_attrs(s, self.dtype, self.attn_kernel),
                                     name="attn")(h)
        elif kind == "conv":
            h = GatedShortConv(s.conv_width, s.rms_eps, self.dtype, name="attn")(h)
        else:
            raise ValueError(f"no attention of kind {kind!r}")
        if self.index < s.dense_layers:  # likewise
            return (DenseLayer(s.dense_width, s.rms_eps, self.dtype, name="mlp")(h), kl, decay,
                    None)
        h, routed = wrap(ExpertLayer, static_argnums=(2,))(
            **expert_attrs(s, self.dtype, self.expert_product_dtype), name="moe")(h, train)
        for name in ("prob", "load"):  # the forward pass's order
            mean = self.variable("batch_stats", f"{name}_mean", jnp.zeros,
                                 (s.n_experts,), jnp.float32)
            if train and not self.is_initializing():
                mean.value = ((1.0 - STATS_MOMENTUM) * mean.value
                              + STATS_MOMENTUM * jax.lax.stop_gradient(routed[name]))
        return h, kl, decay, routed


class TokenEncoder(nn.Module):
    """``[N, H, W, 3] -> [N, spec.hidden]`` float32; see the module docstring."""

    spec: Optional[TokenEncoderSpec] = None
    dtype: Any = jnp.float32
    # each block's sparse attention and expert layer recomputed in the backward
    remat: bool = False
    # attention through ops/sparse_attention.py's kernel pair, the chunked
    # delta rule through ops/delta_rule.py's and the convolution through
    # ops/short_conv.py's: set by train.supcon.build on a one-device TPU
    # mesh; each layer's dtype and shape can still say no
    # (SparseAttention.kernel_reason, GatedDeltaNet.kernel_reason,
    # GatedDeltaNet.conv_reason)
    attn_kernel: bool = False
    # the type of the expert layers' grouped products' operands, ``dtype``
    # where None: set by train.supcon.build likewise (ExpertLayer.product_dtype)
    expert_product_dtype: Any = None

    @property
    def aux_metric_keys(self) -> Tuple[str, ...]:
        """The ring columns this encoder sows beside its ``aux_loss``; an
        encoder without the attribute (a ResNet) sows nothing."""
        return self.spec.ring_columns

    @nn.nowrap
    def read_aux(self, sown: dict):
        """``(aux_loss, {ring column: value})`` from what a train-mode
        ``apply`` sowed into the collection ``aux`` under this module."""
        return sown["aux_loss"], {k: sown[k] for k in self.aux_metric_keys}

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
        aux_loss, sums = jnp.zeros((), jnp.float32), dict.fromkeys(s.ring_columns, 0.0)
        over = dict.fromkeys(s.ring_columns, 0)  # the layers that have each column
        for k in range(s.layers):
            h, kl, decay, routed = Block(s, self.dtype, self.remat, self.attn_kernel, k,
                                         self.expert_product_dtype, name=f"block{k}")(h, train)
            # the loss's terms before the columns' sums, balance before KL: the
            # order of the first block's program, which must not move
            if routed is not None and s.balance_coef:
                aux_loss = aux_loss + s.balance_coef * routed["balance"]
            if kl is not None:
                aux_loss = aux_loss + s.index_coef * kl
                sums["indexer_kl"] += kl
                over["indexer_kl"] += 1
            if routed is not None:
                sums["moe_held_share"] += routed["held_share"]
                sums["moe_load_max_over_mean"] += jnp.max(routed["load"]) * s.n_experts
                if "bias_max_abs" in routed:
                    sums["route_bias_max_abs"] += routed["bias_max_abs"]
                for key in ("moe_held_share", "moe_load_max_over_mean", "route_bias_max_abs"):
                    if key in over:
                        over[key] += 1
            if decay is not None:
                sums["delta_decay_mean"] += decay
                over["delta_decay_mean"] += 1
        z = rms_norm(h, self.param("final_norm", nn.initializers.ones, (s.hidden,)), s.rms_eps)
        keep_last = lambda _, value: value  # noqa: E731
        self.sow(AUX_COLLECTION, "aux_loss", aux_loss, reduce_fn=keep_last, init_fn=lambda: None)
        for key, total in sums.items():
            self.sow(AUX_COLLECTION, key, jax.lax.stop_gradient(total / over[key]),
                     reduce_fn=keep_last, init_fn=lambda: None)
        return jnp.mean(z.astype(jnp.float32), axis=1)


def attention_plan(
    model: str, size: int, owner_reason: Optional[str] = None, **encoder_kwargs
) -> list:
    """One ``{"name", "reason"}`` per sparse-attention layer of ``model``:
    ``reason`` is None where the layer runs ops/sparse_attention.py's kernel
    pair over the patch tokens of ``size x size`` views and otherwise says
    why it stays XLA's: the owner's (``owner_reason``: mesh size, backend) or
    the layer's own ``SparseAttention.kernel_reason``, which is what its
    ``__call__`` asks too. An encoder that is no ``TokenEncoder``, or whose
    preset's layers have another attention, has no such layer."""
    mod = build_encoder(model, **encoder_kwargs)
    if not isinstance(mod, TokenEncoder):
        return []
    layer = SparseAttention(**attention_attrs(mod.spec, mod.dtype, True))
    return [{"name": f"block{k}",
             "reason": owner_reason or layer.kernel_reason((size // mod.spec.patch) ** 2)}
            for k in range(mod.spec.layers) if mod.spec.attention_of(k) == "sparse"]


def _input_projection(s: TokenEncoderSpec) -> tuple:
    """(name, shape) of the first layer's first projection in the preset."""
    kind = s.attention_of(0)
    if kind == "linear":
        return "qkvz", (s.hidden, 2 * s.linear_key_heads * s.linear_key_dim
                        + 2 * s.linear_value_heads * s.linear_value_dim)
    if kind == "conv":
        return "bcx", (s.hidden, 3 * s.hidden)
    width = {"sparse": s.head_dim, "latent": s.nope_dim + s.rope_dim, "gated": 2 * s.head_dim,
             "full": s.head_dim}
    return "q", (s.hidden, s.n_heads * width[kind])


def match_tree(encoder_params: dict) -> Optional[str]:
    """The preset whose parameter tree ``encoder_params`` is, or None: by the
    number of blocks, how many of them are dense, the first layer's first
    projection, and the router's and the held experts' shapes of the last
    block (every preset's last layer has experts)."""
    if "patch_embed" not in encoder_params:
        return None
    blocks = [encoder_params[name] for name in encoder_params if name.startswith("block")]
    moe = encoder_params[f"block{len(blocks) - 1}"].get("moe", {})
    if "router" not in moe:
        return None
    first = blocks[0]["attn"]
    got = (len(blocks), sum("mlp" in b for b in blocks), *moe["router"].shape,
           *moe["w_gate"].shape)
    for name, s in TOKEN_ENCODERS.items():
        projection, shape = _input_projection(s)
        if (projection in first and tuple(first[projection].shape) == shape
                and got == (s.layers, s.dense_layers, s.hidden, s.n_experts, s.held[1], s.hidden,
                            s.expert_width)):
            return name
    return None


MODEL_DICT.update({
    name: (functools.partial(TokenEncoder, spec=spec), spec.hidden)
    for name, spec in TOKEN_ENCODERS.items()
})
