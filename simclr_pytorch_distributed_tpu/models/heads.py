"""Task heads over the ResNet encoder (reference resnet_big.py:159-204).

- ``SupConResNet``: encoder + projection head ('mlp' default: dim->dim->ReLU->128,
  or 'linear'), returning the UNNORMALIZED embedding — L2 normalization happens in
  the train step after the global gather, matching the reference driver
  (``main_supcon.py:283``; head defined at ``resnet_big.py:165-172``).
- ``LinearClassifier``: single linear layer over frozen encoder features
  (``resnet_big.py:196-204``).
- ``SupCEResNet``: encoder + linear classifier for the cross-entropy baseline
  (``resnet_big.py:184-193``; its trainer was lost in the reference fork and is
  rebuilt in ``train/ce.py``).

Linear layers use torch's default init (uniform ±1/sqrt(fan_in) for both kernel
and bias) so the published recipe's init statistics carry over.
"""

from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from simclr_pytorch_distributed_tpu.models import token_encoder
from simclr_pytorch_distributed_tpu.models.resnet import (
    MODEL_DICT,
    Bottleneck,
    ResNet,
    build_encoder,
)


class TorchDense(nn.Module):
    """nn.Dense with torch nn.Linear's default U(±1/sqrt(fan_in)) init."""

    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        fan_in = x.shape[-1]
        bound = 1.0 / (fan_in**0.5)

        def uniform_init(key, shape, dtype=jnp.float32):
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        kernel = self.param("kernel", uniform_init, (fan_in, self.features))
        bias = self.param("bias", uniform_init, (self.features,))
        y = x.astype(self.dtype) @ kernel.astype(self.dtype)
        return y + bias.astype(self.dtype)


class ProjectionHead(nn.Module):
    """'mlp' (dim_in -> dim_in -> ReLU -> feat_dim) or 'linear' head
    (reference resnet_big.py:165-172)."""

    head: str = "mlp"
    dim_in: int = 2048
    feat_dim: int = 128
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if self.head == "linear":
            return TorchDense(self.feat_dim, dtype=self.dtype, name="fc")(x)
        if self.head == "mlp":
            h = TorchDense(self.dim_in, dtype=self.dtype, name="fc1")(x)
            h = nn.relu(h)
            return TorchDense(self.feat_dim, dtype=self.dtype, name="fc2")(h)
        raise NotImplementedError(f"head not supported: {self.head}")


class PredictorHead(nn.Module):
    """BYOL/SimSiam prediction MLP over the projector output
    (dim_out -> hidden -> batch-norm -> ReLU -> dim_out).

    The asymmetric half of the negative-free recipes
    (simclr_pytorch_distributed_tpu/recipes/): the online branch predicts the
    (stop-gradient) target/sibling projection through this head, which is
    what keeps those losses from collapsing — ablating it is the recipes'
    collapse-injection arm (``--byol_predictor none``). The hidden-layer
    batch normalization is the papers' own (BYOL §3.3 / SimSiam §4.4 name
    it as stability-critical, and this repo MEASURED the BN-free variant
    collapsing within 2 tiny epochs — the detector caught it); it
    normalizes by the CURRENT batch's statistics with no running-stat
    tracking, because the predictor only ever runs in train mode — which
    keeps the head's variables in ``params`` alone (no ``batch_stats``
    collection riding the recipe slots).
    """

    dim_hidden: int = 512
    dim_out: int = 128
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, z: jax.Array) -> jax.Array:
        h = TorchDense(self.dim_hidden, dtype=self.dtype, name="fc1")(z)
        mean = jnp.mean(h, axis=0, keepdims=True)
        var = jnp.var(h, axis=0, keepdims=True)
        h = (h - mean) / jnp.sqrt(var + 1e-5)
        h = h * self.param("bn_scale", nn.initializers.ones,
                           (self.dim_hidden,))
        h = h + self.param("bn_bias", nn.initializers.zeros,
                           (self.dim_hidden,))
        h = nn.relu(h)
        return TorchDense(self.dim_out, dtype=self.dtype, name="fc2")(h)


class SupConResNet(nn.Module):
    """Backbone + projection head (reference resnet_big.py:159-181)."""

    model_name: str = "resnet50"
    head: str = "mlp"
    feat_dim: int = 128
    dtype: Any = jnp.float32
    axis_name: Optional[str] = None
    sync_bn: bool = True
    # per-device BN when sync_bn=False: groups = data-parallel degree, views=2
    # (the step's view-major two-crop layout; models/norm.py)
    bn_local_groups: int = 1
    bn_group_views: int = 2
    remat: bool = False  # per-block activation remat (models/resnet.py)
    # Bottleneck's tail through ops/pointwise_bwd.py's one backward kernel:
    # set by train.supcon.build on a one-device TPU mesh (models/resnet.py)
    pointwise_bwd: bool = False
    # a token encoder's attention through ops/sparse_attention.py's kernel
    # pair, its chunked delta rule through ops/delta_rule.py's and its
    # convolution through ops/short_conv.py's: set by train.supcon.build
    # likewise (models/token_encoder.py)
    attn_kernel: bool = False
    # the operands of a token encoder's grouped expert products, ``dtype``
    # where None: set by train.supcon.build likewise (models/experts.py)
    expert_product_dtype: Any = None

    @nn.nowrap
    def build_encoder(self) -> nn.Module:
        """The encoder this model runs, unbound: what ``setup`` binds, and
        what tells the step and the tracing of itself before any ``apply``."""
        return build_encoder(
            self.model_name,
            dtype=self.dtype, axis_name=self.axis_name, sync_bn=self.sync_bn,
            bn_local_groups=self.bn_local_groups,
            bn_group_views=self.bn_group_views,
            remat=self.remat,
            pointwise_bwd=self.pointwise_bwd, attn_kernel=self.attn_kernel,
            expert_product_dtype=self.expert_product_dtype,
        )

    def setup(self):
        self.encoder = self.build_encoder()
        self.proj_head = ProjectionHead(
            head=self.head, dim_in=self.encoder_dim, feat_dim=self.feat_dim,
            dtype=self.dtype,
        )

    @property
    def encoder_dim(self) -> int:
        """Width of the encoder's features: the probe's and the server's input."""
        return MODEL_DICT[self.model_name][1]

    @property
    def aux_metric_keys(self) -> tuple:
        """Metric-ring columns the encoder sows into the collection ``aux``
        beside its ``aux_loss``, as the encoder itself says; none for a
        ResNet, which says nothing."""
        return tuple(getattr(self.build_encoder(), "aux_metric_keys", ()))

    @nn.nowrap
    def read_aux(self, collection: dict):
        """``(auxiliary loss, {ring column: value})`` from the collection
        ``aux`` of a train-mode ``apply``; only where ``aux_metric_keys``."""
        return self.build_encoder().read_aux(collection["encoder"])

    def __call__(self, x: jax.Array, *, train: bool = True) -> jax.Array:
        return self.proj_head(self.encoder(x, train=train))

    def encode(self, x: jax.Array, *, train: bool = False) -> jax.Array:
        """Encoder features only — the probe's frozen feature extractor
        (reference main_linear.py:170-172)."""
        return self.encoder(x, train=train)

    def forward_with_features(self, x: jax.Array, *, train: bool = True):
        """``(projection, encoder_features)`` from ONE backbone forward.

        The online linear probe (train/supcon_step.py) trains on
        ``stop_gradient`` of the encoder features the contrastive forward
        already computes — this method exposes them without a second
        backbone pass (``__call__`` discards the intermediate)."""
        h = self.encoder(x, train=train)
        return self.proj_head(h), h


def infer_architecture_from_variables(variables: dict) -> Tuple[str, str, int]:
    """``(model_name, head, feat_dim)`` from a ``SupConResNet`` params tree.

    The checkpoint layer can restore a ``model`` payload without an abstract
    tree (``utils/checkpoint.load_model_payload``), but consumers still need
    to know WHICH architecture the tree encodes to rebuild the module — this
    reads it off the tree itself (stage block counts + Bottleneck's third
    conv + the proj_head leaf shapes), the orbax-side analogue of
    ``utils/torch_convert.infer_architecture`` for reference state_dicts.
    Accepts ``{'params': ..., ...}`` or a bare params tree.
    """
    params = variables.get("params", variables)
    try:
        enc = params["encoder"]
        head_tree = params["proj_head"]
    except (KeyError, TypeError):
        raise ValueError(
            "variables tree has no encoder/proj_head — not a SupConResNet "
            f"checkpoint (top-level keys: {sorted(params)})"
        )
    name = token_encoder.match_tree(enc)
    if name is not None:
        return (name, *_head_of(head_tree))
    stages = [0, 0, 0, 0]
    for name in enc:
        if m := re.match(r"layer(\d)_block(\d+)$", name):
            layer, block = int(m.group(1)), int(m.group(2))
            stages[layer - 1] = max(stages[layer - 1], block + 1)
    bottleneck = "Conv_2" in enc.get("layer1_block0", {})
    name = next(
        (
            n for n, (ctor, _) in MODEL_DICT.items()
            if isinstance(ctor(), ResNet)
            and tuple(ctor().stage_sizes) == tuple(stages)
            and (ctor().block_cls is Bottleneck) == bottleneck
        ),
        None,
    )
    if name is None:
        raise ValueError(
            f"unrecognized encoder geometry: stages={tuple(stages)}, "
            f"bottleneck={bottleneck}"
        )
    return (name, *_head_of(head_tree))


def _head_of(head_tree: dict) -> Tuple[str, int]:
    if "fc1" in head_tree:
        return "mlp", int(head_tree["fc2"]["kernel"].shape[-1])
    if "fc" in head_tree:
        return "linear", int(head_tree["fc"]["kernel"].shape[-1])
    raise ValueError(f"unrecognized proj_head tree: {sorted(head_tree)}")


class SupCEResNet(nn.Module):
    """Encoder + classifier for supervised CE (reference resnet_big.py:184-193)."""

    model_name: str = "resnet50"
    num_classes: int = 10
    dtype: Any = jnp.float32
    axis_name: Optional[str] = None
    # The reference's surviving CE entry (main_ce.py, a 68-line stub after the
    # fork) never trains, but the trainer it lost carried the same conditional
    # SyncBN conversion as main_supcon.py:223-224 — so the CE path gets the
    # same semantics: sync_bn=True for global-batch statistics, or grouped
    # per-device statistics (models/norm.py) with bn_local_groups = the
    # data-parallel degree. CE batches are single-view: bn_group_views=1.
    sync_bn: bool = True
    bn_local_groups: int = 1
    bn_group_views: int = 1

    def setup(self):
        self.encoder = build_encoder(
            self.model_name,
            dtype=self.dtype, axis_name=self.axis_name, sync_bn=self.sync_bn,
            bn_local_groups=self.bn_local_groups,
            bn_group_views=self.bn_group_views,
        )
        self.fc = TorchDense(self.num_classes, dtype=jnp.float32)

    def __call__(self, x: jax.Array, *, train: bool = True) -> jax.Array:
        return self.fc(self.encoder(x, train=train))


class LinearClassifier(nn.Module):
    """Linear probe over precomputed features (reference resnet_big.py:196-204)."""

    model_name: str = "resnet50"
    num_classes: int = 10

    @nn.compact
    def __call__(self, features: jax.Array) -> jax.Array:
        return TorchDense(self.num_classes, name="fc")(features)
