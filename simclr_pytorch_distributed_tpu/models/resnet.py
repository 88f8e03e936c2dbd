"""CIFAR-variant ResNet family (18/34/50/101), TPU-native NHWC Flax modules.

Architecture parity with the reference ``networks/resnet_big.py``:

- CIFAR stem: single 3x3 stride-1 conv, NO maxpool (reference ``:75-77``);
- four stages of widths 64/128/256/512 with strides 1/2/2/2 (``:78-81``);
- ``BasicBlock`` (expansion 1, ``:7-34``) and ``Bottleneck`` (expansion 4,
  ``:37-67``) with 1x1-conv+BN projection shortcuts on shape change (``:18-23``);
- global average pool + flatten (``:82,116-117``) giving 512 (rn18/34) or 2048
  (rn50/101) features — see ``MODEL_DICT`` (reference ``model_dict :137-142``);
- Kaiming-normal fan-out conv init, BN gamma=1/beta=0 (``:84-89``); optional
  ``zero_init_residual`` zeroing the last BN gamma per block (``:94-99``).

Deliberately NOT carried over (dead code in the reference, SURVEY.md §2.1 #11):
the never-enabled ``is_last``/preact return path, the unused ``layer`` forward
argument, and ``LinearBatchNorm``.

TPU-first choices: NHWC layout (XLA:TPU's native conv layout), fp32 params with
an optional bf16 compute ``dtype`` (convs hit the MXU in bf16; BN statistics stay
fp32 inside ``CrossReplicaBatchNorm``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from simclr_pytorch_distributed_tpu.models.norm import (
    CrossReplicaBatchNorm,
    FusedTrainBN,
)
from simclr_pytorch_distributed_tpu.ops import pointwise_bwd

# planes and first-block stride of the four stages
_STAGE_WIDTHS = (64, 128, 256, 512)
_STAGE_STRIDES = (1, 2, 2, 2)

# torch nn.init.kaiming_normal_(mode='fan_out', nonlinearity='relu')
conv_kernel_init = nn.initializers.variance_scaling(2.0, "fan_out", "normal")


class _ConvKernel(nn.Module):
    """Parameter shadow of ``nn.Conv`` for a site whose backward is a kernel
    (``Bottleneck._tail_one_backward``): owns ONLY the ``kernel`` param, under
    nn.Conv's name/shape/init/param_dtype, so the param tree is the same
    whether or not the kernel engages. Init always traces the ``nn.Conv``
    branch, so this shadow only ever READS the existing param."""

    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("kernel", conv_kernel_init, self.shape, jnp.float32)


def _interpret_pallas() -> bool:
    """Pallas kernels run compiled on TPU, interpreted elsewhere (the CPU
    parity/test path — slow, for correctness only)."""
    return jax.default_backend() != "tpu"


# torch Conv2d(k=3, padding=1) pads (1,1) on each spatial dim. Flax's default
# 'SAME' agrees at stride 1 but at stride 2 XLA pads (0,1), shifting every
# window by one pixel vs torch — weight transplants from the reference would
# silently diverge (caught by tests/test_torch_parity.py). Explicit padding
# pins torch alignment; 1x1 convs use torch's padding=0 ('VALID').
PAD3 = ((1, 1), (1, 1))


class BasicBlock(nn.Module):
    """3x3 + 3x3 residual block, expansion 1 (reference resnet_big.py:7-34)."""

    planes: int
    stride: int = 1
    expansion: int = 1
    dtype: Any = jnp.float32
    norm: Callable[..., nn.Module] = CrossReplicaBatchNorm

    @nn.compact
    def __call__(self, x, train: bool = True):  # train is
        # positional-or-keyword so nn.remat can mark it static (argnum 2)
        norm = partial(self.norm, use_running_average=not train)
        conv = partial(
            nn.Conv, use_bias=False, kernel_init=conv_kernel_init, dtype=self.dtype,
            param_dtype=jnp.float32,
        )
        out = conv(
            self.planes, (3, 3), strides=(self.stride, self.stride), padding=PAD3
        )(x)
        out = nn.relu(norm(name="bn1")(out))
        out = conv(self.planes, (3, 3), padding=PAD3)(out)
        out = norm(name="bn2")(out)

        shortcut = x
        if self.stride != 1 or x.shape[-1] != self.expansion * self.planes:
            shortcut = conv(
                self.expansion * self.planes, (1, 1),
                strides=(self.stride, self.stride), padding="VALID",
                name="shortcut_conv",
            )(x)
            shortcut = norm(name="shortcut_bn")(shortcut)
        return nn.relu(out + shortcut)


class Bottleneck(nn.Module):
    """1x1 -> 3x3 -> 1x1 residual block, expansion 4 (reference resnet_big.py:37-67)."""

    planes: int
    stride: int = 1
    expansion: int = 4
    dtype: Any = jnp.float32
    norm: Callable[..., nn.Module] = CrossReplicaBatchNorm
    # the tail (relu(bn2) -> Conv_2 -> bn3) through ops/pointwise_bwd.py:
    # XLA's forward, one backward kernel. The ResNet owner decides
    # (ResNet.tail_bwd_reason: its attributes and this site's shape); only
    # the mode is left to check here: train, and a traced (not initializing)
    # apply.
    tail_bwd: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):  # train is
        # positional-or-keyword so nn.remat can mark it static (argnum 2)
        norm = partial(self.norm, use_running_average=not train)
        conv = partial(
            nn.Conv, use_bias=False, kernel_init=conv_kernel_init, dtype=self.dtype,
            param_dtype=jnp.float32,
        )
        out = conv(self.planes, (1, 1), padding="VALID")(x)
        out = nn.relu(norm(name="bn1")(out))
        out = conv(
            self.planes, (3, 3), strides=(self.stride, self.stride), padding=PAD3
        )(out)
        c4 = self.expansion * self.planes
        if self.tail_bwd and train and not self.is_initializing():
            out = self._tail_one_backward(out, c4)
        else:
            out = nn.relu(norm(name="bn2")(out))
            out = conv(c4, (1, 1), padding="VALID")(out)
            out = norm(name="bn3")(out)

        shortcut = x
        if self.stride != 1 or x.shape[-1] != self.expansion * self.planes:
            shortcut = conv(
                self.expansion * self.planes, (1, 1),
                strides=(self.stride, self.stride), padding="VALID",
                name="shortcut_conv",
            )(x)
            shortcut = norm(name="shortcut_bn")(shortcut)
        return nn.relu(out + shortcut)

    def _tail_one_backward(self, z, c4: int):
        """bn2 -> ReLU -> Conv_2 -> bn3 on ``z``, the 3x3 conv's output, with
        the parameter shadows under the XLA path's names: same trees, same
        forward, same running-statistic updates."""
        eps = CrossReplicaBatchNorm.epsilon
        count = z.shape[0] * z.shape[1] * z.shape[2]
        bn2 = FusedTrainBN(self.planes, name="bn2")
        scale2, bias2 = bn2()
        mean2, var2 = pointwise_bwd.batch_moments(z)
        bn2(mean2, var2, count)
        kernel = _ConvKernel((1, 1, self.planes, c4), name="Conv_2")()
        bn3 = FusedTrainBN(c4, name="bn3")
        scale3, bias3 = bn3()
        out, mean3, var3 = pointwise_bwd.expand_conv_bn(
            z, mean2, jax.lax.rsqrt(var2 + eps), scale2, bias2, kernel,
            scale3, bias3, eps=eps, interpret=_interpret_pallas(),
        )
        bn3(mean3, var3, count)
        return out


class ResNet(nn.Module):
    """CIFAR-stem ResNet encoder -> [N, feat_dim] (reference resnet_big.py:70-118)."""

    block_cls: Any = Bottleneck
    stage_sizes: Sequence[int] = (3, 4, 6, 3)
    in_channel: int = 3
    dtype: Any = jnp.float32
    axis_name: Optional[str] = None
    sync_bn: bool = True
    # per-device BN groups under GSPMD when sync_bn=False (the reference's
    # default per-GPU BatchNorm2d; see models/norm.py); 1 = whole-batch stats
    bn_local_groups: int = 1
    bn_group_views: int = 1
    # activation rematerialization per residual block: backward recomputes
    # each block's activations instead of keeping them in HBM — the standard
    # FLOPs-for-memory trade for bigger per-chip batches (identical numerics)
    remat: bool = False
    # Bottleneck's tail through one backward kernel (ops/pointwise_bwd.py).
    # Set by the owner that knows the mesh holds ONE device and the backend
    # is a TPU (train.supcon.build); the attributes above and each site's
    # shape can still say no (tail_bwd_reason).
    pointwise_bwd: bool = False

    def block_sites(self):
        """``(name, width, stride)`` of every residual block, in order."""
        for stage, (n_blocks, width, stride) in enumerate(
            zip(self.stage_sizes, _STAGE_WIDTHS, _STAGE_STRIDES)
        ):
            for block in range(n_blocks):
                yield (f"layer{stage + 1}_block{block}", width,
                       stride if block == 0 else 1)

    def tail_bwd_reason(self, rows: int, width: int) -> Optional[str]:
        """Why the Bottleneck of ``width`` planes keeps its tail on XLA's
        backward at a batch of ``rows``, or None: the kernel of
        ops/pointwise_bwd.py is float32 whole-batch BN in one program, at a
        shape it tiles (the stride does not enter: the tail is pointwise).
        ``__call__`` and ``tail_bwd_plan`` both ask here."""
        if self.dtype != jnp.float32:
            return f"compute dtype {jnp.dtype(self.dtype).name}"
        if self.axis_name is not None:
            return f"BN statistics reduced over axis {self.axis_name!r}"
        if not self.sync_bn and self.bn_local_groups > 1:
            return f"BN in {self.bn_local_groups} per-device groups"
        return pointwise_bwd.unsupported(
            rows, width, self.block_cls.expansion * width
        )

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool = True) -> jax.Array:
        norm = partial(
            CrossReplicaBatchNorm, axis_name=self.axis_name, sync=self.sync_bn,
            local_groups=self.bn_local_groups, group_views=self.bn_group_views,
        )
        block_cls = (
            nn.remat(self.block_cls, static_argnums=(2,))
            if self.remat else self.block_cls
        )
        x = x.astype(self.dtype)
        x = nn.Conv(
            64, (3, 3), strides=(1, 1), use_bias=False, padding=PAD3,
            kernel_init=conv_kernel_init, dtype=self.dtype,
            param_dtype=jnp.float32, name="conv1",
        )(x)
        x = nn.relu(norm(use_running_average=not train, name="bn1")(x))
        for name, width, stride in self.block_sites():
            tail = {}
            if self.pointwise_bwd and issubclass(self.block_cls, Bottleneck):
                tail["tail_bwd"] = self.tail_bwd_reason(x.shape[0], width) is None
            x = block_cls(
                planes=width, stride=stride, dtype=self.dtype, norm=norm,
                name=name, **tail,
            )(x, train)
        x = jnp.mean(x, axis=(1, 2))  # global average pool (AdaptiveAvgPool2d((1,1)))
        return x.astype(jnp.float32)


def resnet10(**kwargs) -> ResNet:
    """One BasicBlock per stage — NOT in the reference model_dict
    (resnet_big.py:137-142); an extension for fast smoke tests and small
    experiments where resnet18's compile time dominates."""
    return ResNet(block_cls=BasicBlock, stage_sizes=(1, 1, 1, 1), **kwargs)


def resnet18(**kwargs) -> ResNet:
    return ResNet(block_cls=BasicBlock, stage_sizes=(2, 2, 2, 2), **kwargs)


def resnet34(**kwargs) -> ResNet:
    return ResNet(block_cls=BasicBlock, stage_sizes=(3, 4, 6, 3), **kwargs)


def resnet50(**kwargs) -> ResNet:
    return ResNet(block_cls=Bottleneck, stage_sizes=(3, 4, 6, 3), **kwargs)


def resnet101(**kwargs) -> ResNet:
    return ResNet(block_cls=Bottleneck, stage_sizes=(3, 4, 23, 3), **kwargs)


# name -> (constructor, feature dim); reference model_dict resnet_big.py:137-142.
# The encoder protocol, which every entry keeps (the ResNets here, the token
# encoders that models/token_encoder.py adds): the constructor takes, as
# keywords, those of the owner's flags that its module declares as fields
# (``build_encoder`` hands it no other) and returns a flax module whose
# ``__call__(views [N, H, W, 3], train=...)`` gives ``[N, feature dim]``
# float32 features; running statistics, if it keeps any, live in
# ``batch_stats`` and move in train mode; what it wants added to the loss or
# written to the metric ring it sows into the collection ``aux``.
MODEL_DICT: dict[str, Tuple[Callable[..., nn.Module], int]] = {
    "resnet10": (resnet10, 512),  # test/smoke extension, not in the reference
    "resnet18": (resnet18, 512),
    "resnet34": (resnet34, 512),
    "resnet50": (resnet50, 2048),
    "resnet101": (resnet101, 2048),
}


def build_encoder(model: str, **flags) -> nn.Module:
    """The encoder ``model`` names, built with those of ``flags`` that its
    module declares: a ResNet takes the BN flags, an encoder that has no batch
    norm is not handed them."""
    ctor, _ = MODEL_DICT[model]
    declared = {f.name for f in dataclasses.fields(ctor())}
    return ctor(**{k: v for k, v in flags.items() if k in declared})


def tail_bwd_plan(
    model: str, rows: int, owner_reason: Optional[str] = None, **encoder_kwargs
) -> list:
    """One ``{"name", "reason"}`` per Bottleneck of ``model``: ``reason`` is
    None where the train step's backward goes through ops/pointwise_bwd.py
    and otherwise says why it stays XLA's: the owner's (``owner_reason``:
    mesh size, backend) or the encoder's own ``ResNet.tail_bwd_reason``, which
    is what its ``__call__`` asks too. ``encoder_kwargs`` are the attributes
    the encoder is built with; ``rows`` is its batch, both views of the
    two-crop step. A BasicBlock model has no such site."""
    mod = build_encoder(model, **encoder_kwargs)
    if not (isinstance(mod, ResNet) and issubclass(mod.block_cls, Bottleneck)):
        return []
    return [
        {"name": name, "reason": owner_reason or mod.tail_bwd_reason(rows, width)}
        for name, width, _ in mod.block_sites()
    ]
