"""Distributed contrastive pretraining driver — main_supcon.py, TPU-native.

One process per host drives the SPMD program: build mesh -> data -> model/state
-> jit(augment+step over the mesh) -> epoch loop with meters/TB/checkpoints.
The reference call stack being replaced is SURVEY.md §3.1/§3.2.

Perf notes vs the reference hot loop:
- augmentation + forward + loss + update is ONE compiled program per step; the
  host only permutes uint8 indices (no worker pool, no PIL, no pinned-memory
  staging);
- per-step metrics are written into a device-side ring INSIDE the jitted
  update and flushed as ONE contiguous D2H per ``print_freq`` window on a
  background telemetry thread (utils/telemetry.py), so the hot loop never
  blocks on observability (the reference's per-iter ``loss.item()`` is a sync
  point, ``main_supcon.py:320``) while still metering/TB-logging EVERY step at
  reference cadence;
- checkpoint RESUME is supported (``--resume``), which the reference lacks.
"""

from __future__ import annotations

import contextlib
import logging
import math
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp

from simclr_pytorch_distributed_tpu import config as config_lib
from simclr_pytorch_distributed_tpu import recipes as recipes_lib
from simclr_pytorch_distributed_tpu.data.cifar import (
    ensure_dataset_available,
    load_dataset,
)
from simclr_pytorch_distributed_tpu.data import device_store
from simclr_pytorch_distributed_tpu.data.device_store import slice_epoch_step
from simclr_pytorch_distributed_tpu.data.pipeline import EpochLoader
from simclr_pytorch_distributed_tpu.models import MODEL_DICT, SupConResNet
from simclr_pytorch_distributed_tpu.models.experts import balanced_chunk_rows, provisioned_rows
from simclr_pytorch_distributed_tpu.ops.augment import (
    DATASET_STATS,
    AugmentConfig,
    two_crop_batch,
)
from simclr_pytorch_distributed_tpu.ops import pallas_loss
from simclr_pytorch_distributed_tpu.ops.metrics import AverageMeter
from simclr_pytorch_distributed_tpu.ops.schedules import make_lr_schedule
from simclr_pytorch_distributed_tpu.parallel.mesh import (
    batch_sharding,
    broadcast_from_main,
    create_mesh,
    epoch_buffer_sharding,
    is_main_process,
    replicated_sharding,
    setup_distributed,
    shard_host_batch,
    state_sharding,
    sync_processes,
)
from simclr_pytorch_distributed_tpu.train.state import (
    TrainState,
    create_train_state,
    make_optimizer,
    realign_schedule_count,
)
from simclr_pytorch_distributed_tpu.train.supcon_step import (
    HEALTH_METRIC_KEYS,
    METRIC_KEYS,
    ONLINE_PROBE_METRIC_KEYS,
    SCOPE_AUG,
    SCOPE_DATA,
    SCOPE_RING,
    SupConStepConfig,
    build_online_probe,
    epoch_position,
    extra_columns,
    make_train_step,
    metric_keys,
)
from simclr_pytorch_distributed_tpu.utils.checkpoint import (
    jit_copy_tree,
    load_pretrained_variables,
    resolve_resume_path,
    restore_checkpoint,
    resume_position,
    save_checkpoint,
    wait_for_saves,
)
from simclr_pytorch_distributed_tpu.utils import preempt
from simclr_pytorch_distributed_tpu.utils import profiling
from simclr_pytorch_distributed_tpu.utils import tracing
from simclr_pytorch_distributed_tpu.utils.obs import RunObservability
from simclr_pytorch_distributed_tpu.utils.guard import (
    FailurePolicy,
    NonFiniteLossError,
    check_finite_loss,
    exit_code_for,
    exit_with_code,
)
from simclr_pytorch_distributed_tpu.utils.logging_utils import TBLogger, setup_logging
from simclr_pytorch_distributed_tpu.utils.profiling import StepTracer
from simclr_pytorch_distributed_tpu.utils.telemetry import TelemetrySession


def make_augment_config(cfg: config_lib.SupConConfig, color_ops: bool = True) -> AugmentConfig:
    if cfg.dataset in DATASET_STATS:
        mean, std = DATASET_STATS[cfg.dataset]
    elif cfg.dataset.startswith("synthetic"):
        mean, std = ((0.5, 0.5, 0.5), (0.25, 0.25, 0.25))
    else:  # 'path' datasets: user-supplied strings (reference main_supcon.py:163-165,
        # minus its std=eval(mean) bug)
        mean = tuple(float(x) for x in cfg.mean.strip("()").split(","))
        std = tuple(float(x) for x in cfg.std.strip("()").split(","))
    return AugmentConfig(size=cfg.size, mean=mean, std=std, color_ops=color_ops)


def resolve_loss_impl_reasoned(
    loss_impl: str, batch_size: int, n_devices: int, model_parallel: int = 1,
    moco_queue: int = 0,
) -> tuple:
    """``(resolved_impl, reason)`` — the ``resolve_loss_impl`` ladder with
    the WHY attached, so the driver's startup banner
    (config.impl_resolution_banner) can name a silent degradation
    (unsupported geometry, non-TPU backend) instead of leaving it
    discoverable only by reading this function."""
    if moco_queue and loss_impl == "auto":
        return "dense", (
            f"--moco_queue {moco_queue} extends the contrast side past the "
            "fixed 2B geometry the fused/ring kernels tile"
        )
    if loss_impl != "auto":
        return loss_impl, "explicit request"
    if jax.default_backend() != "tpu":
        return "dense", (
            f"non-TPU backend ({jax.default_backend()}): the fused Pallas "
            "kernel compiles on TPU only"
        )
    data_parallel = max(1, n_devices // max(1, model_parallel))
    if data_parallel == 1:
        if pallas_loss.supports(batch_size, 2):
            return "fused", "TPU single-chip, geometry tiles (+6.6% e2e)"
        return "dense", (
            f"2B={2 * batch_size} does not tile the fused kernel's blocks "
            "(ops/pallas_loss.supports)"
        )
    if pallas_loss.supports_sharded(batch_size, 2, data_parallel):
        return "fused", (
            f"TPU mesh (data={data_parallel}): shard_map-sharded fused "
            "kernel, anchors stay sharded"
        )
    return "dense", (
        f"2B={2 * batch_size} over data={data_parallel} does not tile the "
        "sharded fused kernel (ops/pallas_loss.supports_sharded)"
    )


def resolve_loss_impl(
    loss_impl: str, batch_size: int, n_devices: int, model_parallel: int = 1,
    moco_queue: int = 0,
) -> str:
    """'auto' -> the fused Pallas kernel on TPU, dense otherwise.

    Single chip: the plain fused kernel (+6.6% end-to-end, docs/PERF.md).
    Multi-device mesh: the shard_map-sharded fused kernel — anchors stay
    sharded over 'data', contrast all-gathered, logits tiles VMEM-only
    (ops/pallas_loss.py fused_sharded_supcon_loss) — so 'auto' no longer
    silently downgrades to the O((2B)^2)-materializing dense path on the
    v5e-8 target. Shapes the kernels can't tile fall back to dense, which
    GSPMD partitions as plain HLO.

    ``moco_queue > 0`` forces dense: the queue extends the contrast side to
    ``2B + K``, which the fixed-geometry fused/ring kernels don't tile
    (explicit fused/ring with a queue is rejected at parse,
    config.validate_recipe).
    """
    impl, _ = resolve_loss_impl_reasoned(
        loss_impl, batch_size, n_devices, model_parallel, moco_queue
    )
    return impl


def _one_tpu_reason(n_devices: int) -> Optional[str]:
    """Why a kernel written for one TPU's program stays off this run's path
    whatever the encoder says, or None on a one-device TPU mesh."""
    if n_devices > 1:
        return f"{n_devices} devices in the mesh"
    if jax.default_backend() != "tpu":
        return f"non-TPU backend ({jax.default_backend()})"
    return None


def _reasons(plan: list) -> dict:
    """``{reason: [names]}`` of the sites of a ``[{"name", "reason"}]`` plan
    that stay on XLA's path."""
    reasons: dict = {}
    for site in plan:
        if site["reason"] is not None:
            reasons.setdefault(site["reason"], []).append(site["name"])
    return reasons


def _say_kernel_plan(tag: str, what: str, plan: list) -> list:
    """One banner line ``[tag] N <what>, M on XLA's path; <names>: <why>`` and
    one ``<tag>_plan`` event (track ``compile``: ``engaged``, ``on_xla``,
    ``reasons``) for a ``[{"name", "reason"}]`` plan; returns the plan."""
    reasons = _reasons(plan)
    on_xla = sum(len(names) for names in reasons.values())
    logging.info(
        "[%s] %d %s, %d on XLA's path%s", tag, len(plan) - on_xla, what, on_xla,
        "".join(f"; {', '.join(names)}: {why}" for why, names in reasons.items()),
    )
    tracing.event(
        f"{tag}_plan", track=tracing.COMPILE_TRACK,
        engaged=len(plan) - on_xla, on_xla=on_xla, reasons=reasons,
    )
    return plan


def plan_pointwise_bwd(
    cfg: config_lib.SupConConfig, n_devices: int, **encoder_kwargs
) -> list:
    """``models.resnet.tail_bwd_plan`` for this run, said once in a banner
    line and one ``pointwise_bwd_plan`` event (track ``compile``): how many
    Bottleneck tails take the one-kernel backward of ops/pointwise_bwd.py,
    how many stay on XLA's, and why. No flag chooses: one device, a TPU, and
    what the encoder built with ``encoder_kwargs`` says of itself
    (``ResNet.tail_bwd_reason``: float32, whole-batch BN, a shape the kernel
    tiles within its VMEM budget)."""
    from simclr_pytorch_distributed_tpu.models.resnet import tail_bwd_plan

    return _say_kernel_plan(
        "pointwise_bwd", "Bottleneck tails on one backward kernel",
        tail_bwd_plan(cfg.model, 2 * cfg.batch_size, _one_tpu_reason(n_devices),
                      **encoder_kwargs))


def plan_sparse_attention(
    cfg: config_lib.SupConConfig, n_devices: int, **encoder_kwargs
) -> list:
    """``models.token_encoder.attention_plan`` for this run, said likewise
    (``[sparse_attention]``, ``sparse_attention_plan``): how many attention
    layers run ops/sparse_attention.py's kernel pair, how many stay on XLA's
    path, and why. No flag chooses: one device, a TPU, and what the layer
    built with ``encoder_kwargs`` says of itself
    (``SparseAttention.kernel_reason``: float32, a row the kernels tile
    within their VMEM budget). Silent for an encoder without such layers."""
    from simclr_pytorch_distributed_tpu.models.token_encoder import attention_plan

    plan = attention_plan(cfg.model, cfg.size, _one_tpu_reason(n_devices), **encoder_kwargs)
    if not plan:
        return plan
    return _say_kernel_plan("sparse_attention", "attention layers on the kernel pair", plan)


def plan_latent_attention(cfg: config_lib.SupConConfig, model: SupConResNet):
    """What the latent-attention layers of ``model``'s encoder are, said once
    in a banner line and one ``latent_attention_plan`` event (track
    ``compile``): layers, heads, the three head widths, the latent's rank,
    and the path with its reason. Today the path is always XLA's:
    ops/sparse_attention.py's kernel pair takes one head width for q, k and
    v. None for an encoder without such layers."""
    spec = getattr(model.build_encoder(), "spec", None)
    if spec is None or spec.attention != "latent":
        return None
    plan = {"layers": spec.layers, "heads": spec.n_heads, "nope_dim": spec.nope_dim,
            "rope_dim": spec.rope_dim, "v_dim": spec.v_dim, "kv_rank": spec.kv_rank,
            "tokens": (cfg.size // spec.patch) ** 2, "path": "xla",
            "reason": "no kernel for query/key heads of "
                      f"{spec.nope_dim + spec.rope_dim} beside value heads of {spec.v_dim}"}
    logging.info(
        "[latent_attention] %d layers of %d heads (%d + %d shared rotary / %d), "
        "keys and values out of a latent of %d, %d causal tokens a row; on XLA's "
        "path: %s", plan["layers"], plan["heads"], plan["nope_dim"], plan["rope_dim"],
        plan["v_dim"], plan["kv_rank"], plan["tokens"], plan["reason"])
    tracing.event("latent_attention_plan", track=tracing.COMPILE_TRACK, **plan)
    return plan


def plan_linear_attention(
    cfg: config_lib.SupConConfig, n_devices: int, **encoder_kwargs
) -> list:
    """What the Gated DeltaNet layers of the encoder built with
    ``encoder_kwargs`` are, said once in a banner line and one
    ``linear_attention_plan`` event (track ``compile``): the layers by kind,
    the linear layers' heads and widths, the convolution's taps, the scan's
    chunk, the full layers' heads, the rows a group, and for each linear
    layer the path of its rule and of its convolution with their reasons
    (``per_layer``; ``engaged`` on ops/delta_rule.py's kernel pair and
    ``on_xla``, ``conv_engaged`` on ops/short_conv.py's and
    ``conv_on_xla``). No flag chooses: one device, a TPU, and what the
    layer says of itself (``GatedDeltaNet.kernel_reason``: float32, a chunk
    and head widths the kernels tile within their VMEM budget;
    ``GatedDeltaNet.conv_reason``: float32, channels of whole lanes, rows
    of whole token blocks). Returns ``[{"name", "reason", "conv_reason"}]``
    a linear layer, empty for an encoder without such layers."""
    from simclr_pytorch_distributed_tpu.models.gated_delta import GatedDeltaNet
    from simclr_pytorch_distributed_tpu.models.sparse_attention import ROW_GROUP
    from simclr_pytorch_distributed_tpu.models.token_encoder import build_encoder, delta_attrs

    encoder = build_encoder(cfg.model, **encoder_kwargs)
    spec = getattr(encoder, "spec", None)
    if spec is None or not spec.full_attention_interval:
        return []
    kinds = [spec.attention_of(k) for k in range(spec.layers)]
    tokens = (cfg.size // spec.patch) ** 2
    layer = GatedDeltaNet(**delta_attrs(spec, encoder.dtype, True))
    owner = _one_tpu_reason(n_devices)
    sites = [{"name": f"block{k}", "reason": owner or layer.kernel_reason(tokens),
              "conv_reason": owner or layer.conv_reason(tokens)}
             for k, kind in enumerate(kinds) if kind == "linear"]
    reasons = _reasons(sites)
    conv_reasons = _reasons([{"name": site["name"], "reason": site["conv_reason"]}
                             for site in sites])
    on_xla = sum(len(names) for names in reasons.values())
    conv_on_xla = sum(len(names) for names in conv_reasons.values())
    plan = {"layers": {kind: kinds.count(kind) for kind in dict.fromkeys(kinds)},
            "key_heads": spec.linear_key_heads, "value_heads": spec.linear_value_heads,
            "key_dim": spec.linear_key_dim, "value_dim": spec.linear_value_dim,
            "conv_width": spec.conv_width, "chunk": layer.chunk_of(tokens),
            "full_heads": spec.n_heads, "full_kv_heads": spec.n_kv_heads,
            "full_head_dim": spec.head_dim, "tokens": tokens, "row_group": ROW_GROUP,
            "engaged": len(sites) - on_xla, "on_xla": on_xla,
            "conv_engaged": len(sites) - conv_on_xla, "conv_on_xla": conv_on_xla,
            "per_layer": [{"name": site["name"],
                           "path": "xla" if site["reason"] else "kernel",
                           "reason": site["reason"],
                           "conv_path": "xla" if site["conv_reason"] else "kernel",
                           "conv_reason": site["conv_reason"]} for site in sites]}
    logging.info(
        "[linear_attention] %d Gated DeltaNet layers of %d key / %d value heads of %d / %d, "
        "%d-tap convolution, scan in chunks of %d tokens, beside %d %s layers of %d / %d "
        "heads of %d; %d causal tokens a row, %d rows a group; %d on the kernel pair, "
        "%d on XLA's path%s; convolution: %d on its kernel pair, %d on XLA's path%s",
        len(sites), plan["key_heads"], plan["value_heads"], plan["key_dim"],
        plan["value_dim"], plan["conv_width"], plan["chunk"], len(kinds) - len(sites),
        spec.attention, plan["full_heads"], plan["full_kv_heads"], plan["full_head_dim"],
        tokens, ROW_GROUP, plan["engaged"], on_xla,
        "".join(f"; {', '.join(names)}: {why}" for why, names in reasons.items()),
        plan["conv_engaged"], conv_on_xla,
        "".join(f"; {', '.join(names)}: {why}" for why, names in conv_reasons.items()))
    tracing.event("linear_attention_plan", track=tracing.COMPILE_TRACK, **plan)
    return sites


def expert_product_operands(dtype) -> tuple:
    """``(type, reason)`` of the operands that the expert layers' grouped
    products read: bfloat16 with no reason where the layers are float32 and
    the program a TPU's, which is where default precision rounds the same
    operands to bfloat16 inside every call, after reading them at four bytes
    an element; else ``dtype`` itself and why. No flag chooses, and the number
    of devices does not matter: the call is XLA's own on every chip."""
    if jnp.dtype(dtype) != jnp.float32:
        return dtype, "--bf16"
    if jax.default_backend() != "tpu":
        return dtype, f"non-TPU backend ({jax.default_backend()})"
    return jnp.bfloat16, None


def plan_experts(cfg: config_lib.SupConConfig, model: SupConResNet,
                 product_reason: Optional[str] = None):
    """What the expert layers of ``model``'s encoder hold, said once in a
    banner line and one ``expert_plan`` event (track ``compile``), as
    ``plan_pointwise_bwd`` says its plan, with the dense layers before them,
    the router's rule, the shared experts' width, the type of the grouped
    products' operands (``product_operands``, with ``product_reason`` where
    ``expert_product_operands`` left them as they were), whether a sigmoid
    gates the shared experts (``shared_gate``) and the ring columns
    the encoder sows (``scripts/trace_report.py`` reads their names from the
    event); None for an encoder without experts."""
    spec = getattr(model.build_encoder(), "spec", None)
    if spec is None:
        return None
    first, count = spec.held
    rows = 2 * cfg.batch_size * (cfg.size // spec.patch) ** 2
    provisioned = provisioned_rows(rows * spec.top_k, count, spec.n_experts, spec.capacity_factor)
    trip = min(rows * spec.top_k, balanced_chunk_rows(
        rows * spec.top_k, count, spec.n_experts, provisioned, spec.hidden, spec.expert_width,
        model.dtype))
    plan = {"layers": spec.layers - spec.dense_layers, "held": count, "first": first,
            "n_experts": spec.n_experts, "per_token": spec.top_k,
            "rows_per_step": rows, "capacity_factor": spec.capacity_factor,
            "provisioned_assignments": provisioned,
            "rows_per_trip": trip, "provisioned_trips": -(-provisioned // trip),
            "dense_layers": spec.dense_layers, "router": spec.router,
            "shared_width": spec.shared_width, "shared_gate": spec.shared_expert_gate,
            "product_operands": jnp.dtype(
                model.dtype if model.expert_product_dtype is None
                else model.expert_product_dtype).name,
            "product_reason": product_reason,
            "ring_columns": list(model.aux_metric_keys)}
    logging.info(
        "[experts] %d layers hold experts %d-%d of %d, %d a token (%s-routed "
        "over all %d) after %d dense layers, shared experts of width %d%s beside "
        "them; %d token rows a step, %.1f%% of their assignments land here "
        "when the load is balanced; a layer sweeps %d assignments a step (%.4g "
        "balanced shares, in trips of %d rows: %d) whatever the routing, and "
        "more where more land here; the grouped products read %s operands%s",
        plan["layers"], first, first + count - 1,
        spec.n_experts, spec.top_k, spec.router, spec.n_experts, spec.dense_layers,
        spec.shared_width, " under a sigmoid gate" if spec.shared_expert_gate else "", rows,
        100.0 * count / spec.n_experts, provisioned, spec.capacity_factor,
        trip, plan["provisioned_trips"], plan["product_operands"],
        f" ({product_reason})" if product_reason else "",
    )
    tracing.event("expert_plan", track=tracing.COMPILE_TRACK, **plan)
    return plan


def build(cfg: config_lib.SupConConfig, steps_per_epoch: int, n_devices: int = 1):
    """Model, schedule, optimizer, initial state, and the fused jitted update."""
    dtype = jnp.bfloat16 if cfg.bf16 else jnp.float32
    # --syncBN off = the reference's default per-GPU BatchNorm2d
    # (main_supcon.py:223-224 converts to SyncBN only when the flag is given):
    # BN statistics are scoped to the data-parallel device slices, not the
    # global batch (models/norm.py grouped mode).
    data_parallel = max(1, n_devices // max(1, cfg.model_parallel))
    encoder_kwargs = dict(
        dtype=dtype, sync_bn=cfg.syncBN, remat=cfg.remat,
        bn_local_groups=1 if cfg.syncBN else data_parallel,
    )
    tail_plan = plan_pointwise_bwd(cfg, n_devices, **encoder_kwargs)
    attention_plan = plan_sparse_attention(cfg, n_devices, **encoder_kwargs)
    linear_plan = plan_linear_attention(cfg, n_devices, **encoder_kwargs)
    product_dtype, product_reason = expert_product_operands(dtype)
    model = SupConResNet(
        model_name=cfg.model, head=cfg.head, feat_dim=cfg.feat_dim,
        pointwise_bwd=any(site["reason"] is None for site in tail_plan),
        attn_kernel=any(why is None for layer in attention_plan + linear_plan
                        for why in (layer["reason"], layer.get("conv_reason", layer["reason"]))),
        expert_product_dtype=product_dtype, **encoder_kwargs,
    )
    plan_latent_attention(cfg, model)
    plan_experts(cfg, model, product_reason)
    # --ngpu auto -> the mesh's data-parallel size; an explicit mismatch is
    # promoted from a log-only warning to a startup banner naming the
    # effective-LR consequence (config.ngpu_mismatch_banner)
    grad_div = config_lib.resolve_ngpu(cfg.ngpu, data_parallel)
    if grad_div != data_parallel:
        logging.warning(
            "%s",
            config_lib.ngpu_mismatch_banner(
                grad_div, data_parallel, cfg.learning_rate
            ),
        )
    schedule = make_lr_schedule(
        learning_rate=cfg.learning_rate, epochs=cfg.epochs,
        steps_per_epoch=steps_per_epoch, cosine=cfg.cosine,
        lr_decay_rate=cfg.lr_decay_rate, lr_decay_epochs=cfg.lr_decay_epochs,
        warm=cfg.warm, warm_epochs=cfg.warm_epochs, warmup_from=cfg.warmup_from,
    )
    tx = make_optimizer(
        schedule, momentum=cfg.momentum, weight_decay=cfg.weight_decay,
        optimizer=cfg.optimizer,
    )
    state = create_train_state(
        model, tx, jax.random.key(cfg.seed),
        jnp.zeros((2, cfg.size, cfg.size, 3), jnp.float32),
    )
    loss_impl, loss_reason = resolve_loss_impl_reasoned(
        cfg.loss_impl, cfg.batch_size, n_devices, cfg.model_parallel,
        moco_queue=cfg.moco_queue,
    )
    logging.info(
        "%s",
        config_lib.impl_resolution_banner(
            "loss_impl", cfg.loss_impl, loss_impl, loss_reason
        ),
    )
    step_cfg = SupConStepConfig(
        method=cfg.method, temperature=cfg.temp,
        sec=cfg.sec, sec_wei=cfg.sec_wei, l2reg=cfg.l2reg, l2reg_wei=cfg.l2reg_wei,
        norm_momentum=cfg.norm_momentum, epochs=cfg.epochs,
        steps_per_epoch=steps_per_epoch, grad_div=float(grad_div),
        loss_impl=loss_impl,
        health=cfg.health_freq > 0,
        health_freq=max(1, cfg.health_freq),
        online_probe=cfg.online_probe == "on",
    )
    return model, schedule, tx, state, step_cfg


def attach_online_probe(cfg: config_lib.SupConConfig, state, n_cls: int):
    """``(state_with_probe_slots, OnlineProbe)`` for a ``--online_probe on``
    run: the classifier head + its optimizer (train/supcon_step.py), with
    the trainable probe state attached to the TrainState so it rides the
    jitted update, the donation discipline, and the checkpoint ``probe``
    payload. ``n_cls`` comes from the dataset's own labels, so 'path' trees
    need no extra flag."""
    spec, params, opt_state = build_online_probe(
        cfg.model, MODEL_DICT[cfg.model][1], n_cls, cfg.probe_lr,
        seed=cfg.seed,
    )
    return state.replace(probe_params=params, probe_opt_state=opt_state), spec


def make_fused_update(
    model, tx, schedule, step_cfg, aug_cfg, mesh, state_example,
    metric_ring=None, resident=False, window_batches=None, probe=None,
    recipe=None,
):
    """augment(two crops) + train step as one GSPMD program.

    ``base_key`` is the run's base PRNG key, passed UNCHANGED every step: the
    per-step key is ``fold_in(base_key, state.step)`` INSIDE the program.
    Deriving it on the host (`fold_in` per step) costs a host->device scalar
    transfer per call — ~5 ms/step on the round-5 machine, where it throttled
    the small probe/CE steps (docs/PERF.md); ``state.step`` equals the driver's
    global step, so the key stream (and therefore training) is bit-identical.

    ``metric_ring`` (an ops/metrics.MetricRing) switches the program to ring
    telemetry: ``update(state, ring, images, labels, key) -> (state, ring)``
    with the step's metrics written into row ``state.step % window`` of the
    donated ring instead of being returned as ~7 live device scalars — the
    flush then needs ONE contiguous D2H per window (docs/PERF.md zero-sync
    telemetry). ``None`` keeps the scalar-returning signature (bench.py, the
    dryrun modes, and the distributed-equivalence tests).

    ``resident`` switches the data arguments from one host-fed batch to the
    device-resident ``[steps, batch, ...]`` epoch buffers
    (data/device_store.py): the program slices its own batch at
    ``state.step % steps_per_epoch`` (train/supcon_step.epoch_position) so
    the hot loop carries NO per-step host work or transfer. The buffers are
    deliberately NOT donated — every step of the epoch reads them.
    ``window_batches`` (with ``resident=True``) narrows the buffers to one
    streaming ``[window_batches, batch, ...]`` window (a WindowStore): the
    in-program position becomes ``epoch_position % window_batches``, valid
    because windows are aligned to multiples of the window length.

    ``probe`` (an OnlineProbe, required iff ``step_cfg.online_probe``) adds
    the detached online-probe update to the same compiled program
    (train/supcon_step.py) — its metrics ride the ring like everything else.

    ``recipe`` (a recipes/ Recipe) swaps the loss head inside the same
    compiled program — predictor update, EMA transition, and queue rotation
    all ride the one dispatch (train/supcon_step.make_train_step). ``None``
    keeps the pre-recipe inline contrastive step.
    """
    train_step = make_train_step(
        model, tx, schedule, step_cfg, mesh=mesh, probe=probe, recipe=recipe
    )
    repl = replicated_sharding(mesh)
    state_sh = state_sharding(mesh, state_example)
    if resident:
        data_sh = (
            epoch_buffer_sharding(mesh, 5), epoch_buffer_sharding(mesh, 2),
        )
    else:
        data_sh = (batch_sharding(mesh, 4), batch_sharding(mesh, 1))

    def core(state: TrainState, images_arg, labels_arg, base_key):
        with jax.named_scope(SCOPE_DATA):
            if resident:
                pos = epoch_position(state.step, step_cfg.steps_per_epoch)
                if window_batches is not None:
                    pos = pos % window_batches
                images_u8, labels = slice_epoch_step(
                    images_arg, labels_arg, pos
                )
            else:
                images_u8, labels = images_arg, labels_arg
            key = jax.random.fold_in(base_key, state.step)
        with jax.named_scope(SCOPE_AUG):
            views = two_crop_batch(key, images_u8, aug_cfg)
        return train_step(state, views, labels)

    if metric_ring is None:
        return jax.jit(
            core,
            in_shardings=(state_sh, *data_sh, repl),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,),
        )

    def ring_update(state: TrainState, ring, images_arg, labels_arg, base_key):
        new_state, metrics = core(state, images_arg, labels_arg, base_key)
        with jax.named_scope(SCOPE_RING):
            return new_state, metric_ring.write(ring, metrics, state.step)

    update = jax.jit(
        ring_update,
        in_shardings=(state_sh, repl, *data_sh, repl),
        out_shardings=(state_sh, repl),
        donate_argnums=(0, 1),
    )
    # the driver loop's program, findable by whoever reads a profile
    # (StepTracer, the benchmark's readers): nothing is lowered here
    profiling.register_step_program(ring_update.__name__, update)
    return update


TB_ITER_SCALARS = (  # reference per-iter scalars, main_supcon.py:327-333
    "norm_mean", "norm_var", "record_norm_mean", "loss_sec", "loss_l2reg",
)

# training-health TB tags (docs/OBSERVABILITY.md "Training health"): the
# ring's health/probe columns, logged at the TRUE global step like info/*
# so a collapse correlates directly against the loss curves. NaN sentinel
# rows (non-health steps) are skipped host-side. Recipe metric columns
# (recipes/: the VICReg term breakdown) land under recipe/* — the static
# map covers every recipe's keys; runs without them simply never match.
EXTRA_TB_TAGS = {
    **{k: "health/" + k[len("health_"):] for k in HEALTH_METRIC_KEYS},
    **{k: "probe/" + k[len("probe_"):] for k in ONLINE_PROBE_METRIC_KEYS},
    **{k: "recipe/" + k for k in recipes_lib.ALL_RECIPE_METRIC_KEYS},
}


def train_one_epoch(
    epoch, loader, update_fn, state, mesh, base_key, cfg, tb, steps_per_epoch,
    tracer=None, start_step=0, telemetry=None, store=None, compile_span=False,
    health_monitor=None, gauges=None,
):
    """One epoch (reference train(), main_supcon.py:242-351).

    Metric handling: the jitted update writes every step's metrics into a
    device-side ring (``update_fn(state, ring, images, labels, key)``); at
    each ``print_freq`` boundary the ring is SNAPSHOTTED (device-side copy —
    later steps donate the ring buffer) and the window job — ONE contiguous
    D2H, NaN check, meters, TB, the progress log line — runs on the
    telemetry executor. With ``--telemetry async`` (default) the main thread
    never blocks on observability; ``sync`` runs the same job inline (the
    pre-ring semantics). Either way the reference's observability contract
    holds — ``info/*`` TB scalars every iteration (main_supcon.py:327-333)
    and a loss meter averaging ALL steps (main_supcon.py:320) — without the
    reference's per-iter ``.item()`` sync point.

    ``start_step > 0`` is the mid-epoch resume path: the loader skips the
    already-consumed prefix of the epoch's deterministic permutation and the
    step indices continue from where the preempted run stopped (``state.step``
    was restored from the checkpoint, so the in-program per-step PRNG keys
    line up with the uninterrupted run). The ring is transient (never
    checkpointed); a fresh one is created here each epoch.

    ``store`` (a data/device_store DeviceStore or WindowStore) switches the
    epoch to the device-resident data path: every step dispatches against
    the resident buffers ``store.batch_buffers(epoch, idx)`` returns — the
    whole cached epoch for a DeviceStore (one index upload + compiled
    shuffle-gather at epoch start), or the streaming window containing
    ``idx`` for a WindowStore (one H2D per window, the next window staged
    by its prefetch thread) — while ``update_fn`` (built with
    ``resident=True``) slices its own batch from them on device. No host
    gather, no per-step H2D either way. The permutation source is the same
    ``loader``, so batch composition is bit-identical in every placement;
    under resume the slice position follows the restored step counter, so
    ``start_step`` only sets where this host loop begins (and which window
    is fetched first).

    Each flush boundary also checks the preemption flag (utils/preempt.py)
    ON THE MAIN THREAD — the collective decision never depended on the D2H
    completing; the executor is drained before returning so the emergency
    checkpoint in :func:`run` sees complete meters. A non-finite loss
    detected by a background flush re-raises here at the next boundary (at
    most one window late; docs/RESILIENCE.md).

    Returns ``(state, loss_avg, last_metrics, preempted_at)`` where
    ``preempted_at`` is the number of epoch steps completed when preemption
    was observed, or ``None`` for a full epoch.
    """
    owns_telemetry = telemetry is None
    if owns_telemetry:
        telemetry = TelemetrySession(
            cfg.print_freq,
            metric_keys(health=cfg.health_freq > 0,
                        online_probe=cfg.online_probe == "on",
                        extra=recipes_lib.recipe_metric_keys(
                            getattr(cfg, "recipe", "simclr"))),
            cfg.telemetry,
        )
    batch_time, data_time, losses = AverageMeter(), AverageMeter(), AverageMeter()
    end = time.time()
    last_host = {}  # most recently flushed metrics, as python floats
    bsz = cfg.batch_size
    telemetry.start_window_clock()
    ring_buf = telemetry.init_buffer(replicated_sharding(mesh))
    # host seconds inside the window's update_fn(...) calls: [sum, min, max].
    # Two clock reads a step into this local; the boundary's flush_boundary
    # span takes it as attributes — still no record between boundaries.
    # Dispatch is asynchronous, so past the enqueue cost (the min) this is
    # back-pressure: the host waiting for room in the device's queue.
    dispatch = [0.0, math.inf, 0.0]
    # the run's columns beyond the step's own, from the ring itself: the
    # recipe's and the encoder's (recipes.attach_for_config). The monitor
    # averages them into its windows; those no tag names go under encoder/
    extra = extra_columns(telemetry.ring.keys)
    tb_tags = {**{k: "encoder/" + k for k in extra}, **EXTRA_TB_TAGS}
    if health_monitor is not None:
        health_monitor.extra_keys = extra

    def submit_window(boundary_idx, step_hint):
        """One ``flush_boundary`` (utils/telemetry.py: meter the window on
        the main thread — same aggregate semantics as the reference's
        per-iter meter, main_supcon.py:336-337, amortized over print_freq
        steps — snapshot + queue the one-transfer flush, observe failures
        collectively). The job NaN-checks, meters, TB-logs every step, and
        emits the progress line. ``bt`` arrives snapshotted from the main
        thread (flush_boundary), and ``dt`` is snapshotted here at the
        boundary: the main thread keeps mutating both meters while the
        async job runs, so a worker-side read would log a later window's
        (possibly torn) numbers."""
        dt = (data_time.val, data_time.avg)

        def consume(fetched, bt):
            for (idx_f, gstep_f), m in fetched:
                check_finite_loss(m["loss"], gstep_f, cfg.nan_guard)
                losses.update(m["loss"], bsz)
                if is_main_process() and tb is not None:
                    # the TRUE global step — same coordinate as the tracer,
                    # the checkpoint meta, and the preemption/rollback log
                    # lines, so a failure event correlates directly against
                    # the curves
                    it = (epoch - 1) * steps_per_epoch + idx_f
                    for name in TB_ITER_SCALARS:
                        tb.log_value(f"info/{name}", m[name], it)
                    for name, tag in tb_tags.items():
                        # NaN = the lax.cond sentinel for a non-health step
                        if name in m and math.isfinite(m[name]):
                            tb.log_value(tag, m[name], it)
                last_host.clear()
                last_host.update(m)
            if health_monitor is not None:
                # windowed collapse/divergence evaluation (utils/guard.py):
                # emits health_window/health_alarm recorder events, stamps
                # the sidecar gauges, and under --health_policy abort raises
                # here on the telemetry thread — surfaced COLLECTIVELY at
                # the next boundary as failure code 3, like the NaN check
                health_monitor.ingest(
                    [(gstep_f, m) for (_, gstep_f), m in fetched],
                    gauges=gauges,
                )
            logging.info(
                "Train: [%d][%d/%d]\tBT %.3f (%.3f)\tDT %.3f (%.3f)\t"
                "loss %.3f (%.3f)\tnorm_mean %.3f (record: %.3f) var %.3f",
                epoch, boundary_idx + 1, steps_per_epoch,
                bt[0], bt[1], dt[0], dt[1], losses.val, losses.avg,
                last_host["norm_mean"], last_host["record_norm_mean"],
                last_host["norm_var"],
            )

        timed = dispatch[1] < math.inf  # the window timed a step
        telemetry.flush_boundary(ring_buf, consume, batch_meter=batch_time,
                                 step_hint=step_hint,
                                 dispatch=tuple(dispatch) if timed else None)
        dispatch[:] = [0.0, math.inf, 0.0]

    def epoch_loss_avg():
        return losses.avg if losses.count else last_host.get("loss", 0.0)

    # both loop shapes iterate range(start_step, steps_per_epoch) — an
    # oversized resume offset (changed geometry) must raise, not silently
    # complete a zero-step epoch
    loader.check_start_step(start_step)
    batches = None if store is not None else loader.epoch(
        epoch, start_step=start_step
    )
    try:
        for idx in range(start_step, steps_per_epoch):
            if batches is not None:
                images_u8, labels = next(batches)
            data_time.update(time.time() - end)  # resident: nothing staged
            global_step = (epoch - 1) * steps_per_epoch + idx
            # the ONE per-run instrumented dispatch: the first call's
            # duration is dominated by trace+XLA compile (dispatch is async,
            # so steady-state calls return in microseconds) — the flight
            # recorder's main:compile phase, wrapped around ONLY the update
            # call (the store's epoch_gather/window_swap record on main:data,
            # and main:* phase spans never nest across tracks). Every later
            # step takes the nullcontext arm: no span records in the hot
            # loop.
            compiling = compile_span and idx == start_step
            span = (
                tracing.span("first_step", track="main:compile",
                             step=global_step)
                if compiling else contextlib.nullcontext()
            )
            # per-step key = fold_in(base_key, state.step) INSIDE the program
            # (state.step == global_step); see make_fused_update
            if batches is None:
                data_args = store.batch_buffers(epoch, idx)
            else:
                data_args = shard_host_batch((images_u8, labels), mesh)
            t_dispatch = time.perf_counter()
            with span:
                state, ring_buf = update_fn(
                    state, ring_buf, data_args[0], data_args[1], base_key
                )
            if compiling:
                # once a run: what every LATER call looks like in the
                # abstract (the state and ring this call returned, committed
                # to the program's own shardings), so that a profile's reader
                # can ask for the text of the program the steady steps run.
                # Not this call's own arguments: a fresh state is
                # uncommitted, and run() compiles the update a second time
                # for the committed one
                profiling.note_step_signature(
                    (state, ring_buf, data_args[0], data_args[1], base_key)
                )
            else:  # the compiling call is main:compile's
                took = time.perf_counter() - t_dispatch
                dispatch[0] += took
                dispatch[1] = min(dispatch[1], took)
                dispatch[2] = max(dispatch[2], took)
            telemetry.append((idx, global_step), global_step)
            if tracer is not None:
                tracer.step(global_step)

            if (idx + 1) % cfg.print_freq == 0 or idx + 1 == steps_per_epoch:
                submit_window(idx, global_step)
                if idx + 1 < steps_per_epoch and preempt.requested_global():
                    # collective decision — every process calls
                    # requested_global at this same deterministic boundary
                    # (main thread; independent of any in-flight flush), so
                    # all hosts commit to the same preemption step (a
                    # lone-host observation would deadlock the collective
                    # save against peers' train steps). Drain COLLECTIVELY
                    # (drain_global — a host-local raise here would skip the
                    # collective emergency save in run() while peers enter
                    # it) so the meters and that checkpoint see complete
                    # metrics. The last-step boundary falls through instead —
                    # that preemption is an ordinary epoch-boundary save.
                    telemetry.drain_global(global_step)
                    return state, epoch_loss_avg(), dict(last_host), idx + 1
            end = time.time()

        # flush any short-epoch tail, then drain COLLECTIVELY — the
        # epoch-boundary save that follows is collective too (the ordering
        # contract lives on the session)
        telemetry.finish_epoch(
            lambda hint: submit_window(steps_per_epoch - 1, hint),
            epoch * steps_per_epoch - 1,
        )
        return state, epoch_loss_avg(), dict(last_host), None
    finally:
        if batches is not None:
            # an early return (preemption) or a raise abandons the loader's
            # generator mid-epoch; close() stops its prefetch worker
            # (data/pipeline.py handles GeneratorExit) instead of leaving it
            # blocked in q.put()
            batches.close()
        if owns_telemetry:
            telemetry.close()


def enable_compile_cache() -> str:
    """Persistent XLA compile cache, placed from outside: returns its dir.

    ``JAX_COMPILATION_CACHE_DIR`` set -> jax has already read it; no place
    is set in code. Unset -> ``<checkout>/.jax_cache``, one fixed path for the
    trainers, the server, bench.py and chip_smoke.py (a dir that moves
    with ``--workdir`` never hits).

    The drivers call it first after their imports and flag parsing, so its
    entry closes the set-up span ``import`` where the program's first ask of
    the backend has not (``parallel/mesh.py``), and from here every
    program's trace, lowering and compile is recorded (utils/tracing.py),
    into the module's buffer until the run's recorder is installed.
    """
    tracing.imports_done()
    tracing.forward_compile_events()
    # Scopes and module paths are metadata, which the cache's key leaves out
    # by default: an executable cached by a build with OTHER scopes (or none)
    # would be loaded with its stale op_names, and the per-scope reduction of
    # a profile (benchmark/scope_reduce.py) would read them. With metadata
    # in the key a hit is always this build's own names. Metadata is also
    # each instruction's source file and line, which would make every line
    # shift in a file the step is traced through a cold compile: with no
    # traceback frames in the locations the metadata is the op_name alone.
    # The price: compiled programs name no source line (xprof's source
    # view, XLA's own error messages).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def run(cfg: config_lib.SupConConfig) -> TrainState:
    setup_distributed()
    # collective saves need every process writing into process 0's run folder
    # (the timestamped name is derived per-process, mesh.broadcast_from_main)
    cfg.save_folder = broadcast_from_main(cfg.save_folder)
    cfg.tb_folder = broadcast_from_main(cfg.tb_folder)
    enable_compile_cache()
    setup_logging(cfg.save_folder, is_main_process())
    mesh = create_mesh(model_parallel=cfg.model_parallel)
    logging.info("mesh: %s over %d devices", dict(mesh.shape), mesh.size)

    ensure_dataset_available(cfg.dataset, cfg.data_folder, cfg.download)
    train_data, _, _ = load_dataset(
        cfg.dataset, cfg.data_folder,
        allow_synthetic_fallback=(cfg.dataset == "synthetic"), size=cfg.size,
        store_size=cfg.store_size, mmap_threshold_mb=cfg.mmap_threshold_mb,
    )
    loader = EpochLoader(
        train_data["images"], train_data["labels"], cfg.batch_size,
        base_seed=cfg.seed, process_index=jax.process_index(),
        process_count=jax.process_count(),
    )
    steps_per_epoch = len(loader)
    # Observability stack (docs/OBSERVABILITY.md, utils/obs.py): the flight
    # recorder writes host-boundary spans to <save_folder>/events.jsonl
    # (+ a Chrome-trace export on close), the stall watchdog turns a
    # non-advancing flush boundary into stack-dump artifacts, and the
    # optional Prometheus sidecar exposes liveness gauges. All host-only:
    # the dispatch-only hot loop gains zero device syncs or transfers
    # (asserted mechanically in tests/test_tracing.py). Built BEFORE the
    # store: placement resolution is the run's FIRST collective, and its
    # placement_decision span + startup clock anchor (the fleet report's
    # alignment ruler, trace_report --fleet) must land on the record.
    obs = RunObservability(cfg, name="supcon")
    # --data_placement: 'device' keeps the uint8 dataset HBM-resident,
    # 'window' streams a double-buffered window (one H2D per window), and
    # 'auto' walks the device->window->host ladder against the budget
    # (--device_budget_mb overrides it) with a startup banner naming any
    # degradation (data/device_store.py)
    try:
        store = device_store.make_store(
            cfg.data_placement, loader, mesh,
            budget_bytes=device_store.budget_override_bytes(cfg.device_budget_mb),
            window_batches=cfg.data_window_batches,
        )
    except BaseException as e:
        # the placement rejection (an explicit --data_placement the
        # budget/ladder refuses) is a DESIGNED raise path that sits
        # before the driver's main try/finally: close the stack here
        # so the recorder still exports and the terminal exit code
        # stamps (the startup-failure post-mortem the stack exists for)
        obs.close(exit_code=exit_code_for(e))
        raise
    obs.staged()  # staging done: reset the watchdog deadline (utils/obs.py)
    # build() emits the loss_impl resolution banner
    model, schedule, tx, state, step_cfg = build(cfg, steps_per_epoch, mesh.size)
    # --recipe: the SSL loss head + its TrainState slots (recipes/). Attach
    # BEFORE any resume restore so the abstract state carries the recipe
    # slots (the probe convention below); slot-free recipes leave the state
    # untouched. The recorded run_recipe event is what offline readers
    # (scripts/health_report.py) key their per-recipe thresholds on.
    state, recipe = recipes_lib.attach_for_config(
        cfg, model, state, schedule=schedule
    )
    logging.info(
        "recipe: %s%s", recipe.name,
        f" (moco_queue={cfg.moco_queue})" if cfg.moco_queue else "",
    )
    probe = None
    if cfg.online_probe == "on":
        # attach BEFORE any resume restore: the abstract state then carries
        # the probe slots, so restore_checkpoint brings the probe payload
        # back (or degrades to the fresh init with a warning)
        state, probe = attach_online_probe(
            cfg, state, int(train_data["labels"].max()) + 1
        )
        logging.info(
            "online probe: %d-class linear head on stop_gradient encoder "
            "features (lr %g)", int(train_data["labels"].max()) + 1,
            cfg.probe_lr,
        )

    start_epoch, start_step = 1, 0
    if cfg.ckpt:
        # warm start: model variables only (main_supcon.py:216-220)
        variables = load_pretrained_variables(
            cfg.ckpt, {"params": state.params, "batch_stats": state.batch_stats}
        )
        state = state.replace(
            params=variables["params"], batch_stats=variables["batch_stats"]
        )
        logging.info("load model from %s ...", cfg.ckpt)
    meta = {}
    if cfg.resume:
        resume_path = resolve_resume_path(cfg.resume)
        # mesh= makes the restore ELASTIC: orbax reshards onto THIS run's
        # mesh on load, so a checkpoint saved under a different device
        # count resumes here (the supervisor's restart-resized decision;
        # _warn_mesh_change names the BN/ngpu consequences). recipe= is the
        # cross-recipe hygiene key: a checkpoint whose recorded recipe
        # differs restores the encoder trajectory but degrades the recipe
        # slots to fresh init, loudly (utils/checkpoint.py).
        state, meta = restore_checkpoint(
            resume_path, state, mesh=mesh, recipe=recipe.name,
            moco_queue=cfg.moco_queue,
        )
        # mid-epoch emergency save (utils/preempt.py): re-enter the epoch at
        # the first unconsumed batch of its deterministic permutation
        start_epoch, start_step = resume_position(meta, steps_per_epoch)
        logging.info(
            "resumed from %s at epoch %d step %d",
            resume_path, start_epoch, start_step,
        )

    aug_cfg = make_augment_config(cfg)
    # One telemetry session per run: the device-side metric ring (written
    # inside the jitted update) + the background flush executor the epoch
    # loop hands each print_freq window to (utils/telemetry.py). The
    # watchdog/gauges ride its flush boundaries.
    telemetry = TelemetrySession(
        cfg.print_freq,
        metric_keys(health=step_cfg.health, online_probe=step_cfg.online_probe,
                    extra=recipe.metric_keys),
        cfg.telemetry,
        watchdog=obs.watchdog, gauges=obs.gauges,
    )
    # durable recipe marker on the recorder stream: offline readers
    # (scripts/health_report.py) pick their per-recipe collapse signatures
    # off this event instead of guessing from the metric columns
    tracing.event(
        "run_recipe", track="main:guard", recipe=recipe.name,
        moco_queue=cfg.moco_queue,
    )

    def build_update(lr_scale: float):
        """The fused jitted update; ``lr_scale != 1`` (the NaN-rollback
        damping) rescales the whole schedule — optimizer chain structure is
        unchanged, so existing opt_states restore into it directly."""
        store_kwargs = dict(
            resident=store is not None,
            window_batches=None if store is None else store.window_batches,
            probe=probe, recipe=recipe,
        )
        if lr_scale == 1.0:
            return make_fused_update(
                model, tx, schedule, step_cfg, aug_cfg, mesh, state,
                metric_ring=telemetry.ring, **store_kwargs,
            )
        scaled = lambda s, sc=lr_scale: schedule(s) * sc  # noqa: E731
        return make_fused_update(
            model,
            make_optimizer(
                scaled, momentum=cfg.momentum,
                weight_decay=cfg.weight_decay, optimizer=cfg.optimizer,
            ),
            scaled, step_cfg, aug_cfg, mesh, state,
            metric_ring=telemetry.ring, **store_kwargs,
        )

    # failure policy (utils/guard.py): what a NonFiniteLossError does to the
    # run. Rollback damping is RUN state, not config — it rides checkpoint
    # meta (extra_meta below) so a preempted/crashed run resumes at the
    # damped LR with its rollback budget intact, instead of silently
    # reverting to the LR that NaN'd in the first place.
    policy = FailurePolicy(cfg.nan_policy)
    try:
        policy.lr_scale = float(meta.get("lr_scale") or 1.0)
        policy.rollbacks = int(meta.get("rollbacks") or 0)
    except (TypeError, ValueError):
        pass  # hand-edited meta: keep the fresh policy
    if policy.lr_scale != 1.0:
        logging.warning(
            "resumed with rollback damping: lr_scale %.3g after %d "
            "rollbacks", policy.lr_scale, policy.rollbacks,
        )

    def policy_meta():
        # the recipe name/queue geometry ride checkpoint meta so a resume
        # under a DIFFERENT recipe is detectable (utils/checkpoint.py
        # cross-recipe hygiene) without probing payload tree structure
        return {"lr_scale": policy.lr_scale, "rollbacks": policy.rollbacks,
                "recipe": recipe.name, "moco_queue": cfg.moco_queue}

    update_fn = build_update(policy.lr_scale)
    with tracing.span("tb_writer", track=tracing.SETUP_TRACK):
        tb = TBLogger(cfg.tb_folder, enabled=is_main_process())
    base_key = jax.random.key(cfg.seed + 1)
    tracer = StepTracer(
        cfg.trace_dir, cfg.trace_start_step, cfg.trace_steps,
        enabled=is_main_process(),
    )

    # The per-epoch crash backup as ONE jitted program: mapping bare
    # ``jnp.copy`` over the tree dispatches ~30 op-by-op ``jit(copy)``
    # programs whose caches all miss AGAIN at epoch 2 (the post-update state
    # carries mesh shardings the fresh epoch-1 state lacked), costing ~20 s
    # of sub-second compiles that the persistent cache never keeps. One
    # program = one compile per sharding layout, persisted across runs —
    # shared with the restore path's buffer re-owning copy.
    copy_state = jit_copy_tree

    # NOTE on preemption in multi-process jobs: the decision to stop is
    # collective (preempt.requested_global), so the emergency save below
    # sees all processes arrive (docs/RESILIENCE.md).
    preempt.install()
    # captured explicitly for the terminal exit-code gauge: sys.exc_info()
    # inside the finally would also see an exception being HANDLED in an
    # enclosing frame (a caller's retry wrapper), misclassifying a clean
    # run as that outer failure
    exit_exc = None
    try:
        for epoch in range(start_epoch, cfg.epochs + 1):
            t1 = time.time()
            ss = start_step if epoch == start_epoch else 0
            # The update donates the incoming state's buffers, so the pre-epoch
            # `state` object is DELETED after the first step — an un-donated
            # on-device copy (one HBM->HBM copy per epoch) is what the crash
            # handler can still save.
            with tracing.span("epoch_backup", track="main:checkpoint",
                              epoch=epoch):
                backup = copy_state(state) if cfg.nan_guard else None
            obs.set_epoch(epoch)
            try:
                with tracing.span("epoch", track="main:epoch", epoch=epoch):
                    state, loss_avg, metrics, preempted_at = train_one_epoch(
                        epoch, loader, update_fn, state, mesh, base_key, cfg,
                        tb, steps_per_epoch, tracer=tracer, start_step=ss,
                        telemetry=telemetry, store=store,
                        compile_span=(epoch == start_epoch),
                        health_monitor=obs.health, gauges=obs.gauges,
                    )
            except NonFiniteLossError:
                # emergency save of the epoch-top state so --resume can
                # restart after the root cause is addressed (failure
                # detection, SURVEY.md §5 — absent upstream). step_in_epoch
                # = ss: after a mid-epoch resume the backup sits mid-epoch,
                # and a resume from this save must not replay consumed
                # batches. NOTE: orbax multi-process saves are collective —
                # EVERY process calls save_checkpoint (orbax coordinates who
                # writes; meta.json is process-0-gated inside); only logging
                # stays process-0.
                save_checkpoint(
                    cfg.save_folder, f"crash_epoch_{epoch}", backup,
                    config=config_lib.config_dict(cfg), epoch=epoch - 1,
                    step_in_epoch=ss, extra_meta=policy_meta(),
                )
                if is_main_process():
                    logging.error("non-finite loss: saved crash_epoch_%d", epoch)
                if not policy.should_rollback():
                    raise
                # --nan_policy rollback: restore the epoch-boundary backup,
                # SKIP the poisoned epoch (the step counter jumps to this
                # epoch's end so the LR schedule position and the per-step
                # PRNG stream stay aligned with the epoch number), damp the
                # LR, and keep training. The applied LR reads the
                # optimizer's OWN ScaleByScheduleState counter, so the jump
                # must realign that too — not just state.step — or the
                # schedule silently lags the skip.
                target = epoch * steps_per_epoch
                state = backup.replace(
                    step=backup.step + (target - int(backup.step)),
                    opt_state=realign_schedule_count(backup.opt_state, target),
                )
                tracing.event(
                    "nan_rollback", track="main:guard", epoch=epoch,
                    rollbacks=policy.rollbacks, lr_scale=policy.lr_scale,
                )
                update_fn = build_update(policy.lr_scale)
                logging.warning(
                    "nan_policy=rollback (%d/%d): epoch %d skipped from its "
                    "boundary backup, lr scaled to %.3g",
                    policy.rollbacks, policy.max_rollbacks, epoch,
                    policy.lr_scale,
                )
                continue
            if preempted_at is not None:
                # SIGTERM/SIGINT observed (collectively) at a flush boundary
                # mid-epoch: blocking emergency save carrying the intra-epoch
                # position, then the distinct exit code. run()'s finally
                # still drains/uninstalls/closes on the way out.
                tracing.event(
                    "preempt_exit", track="main:guard", epoch=epoch,
                    step_in_epoch=preempted_at,
                )
                preempt.emergency_save_and_exit(
                    cfg.save_folder,
                    f"preempt_epoch_{epoch}_step_{preempted_at}", state,
                    config_lib.config_dict(cfg), epoch - 1,
                    step_in_epoch=preempted_at, extra_meta=policy_meta(),
                )
            t2 = time.time()
            # the schedule is evaluated eagerly here (a device round trip on
            # a queue the drain has just emptied): its own phase, so the
            # epoch-edge idle gap is not put down to "no span open"
            with tracing.span("epoch_log", track="main:log", epoch=epoch):
                logging.info("epoch %d, total time %.2f", epoch, t2 - t1)
                if is_main_process():
                    tb.log_value("loss", loss_avg, epoch)
                    tb.log_value(
                        "learning_rate",
                        float(schedule((epoch - 1) * steps_per_epoch))
                        * policy.lr_scale,
                        epoch,
                    )
            if epoch % cfg.save_freq == 0:
                # collective on all processes (see crash handler note); async
                # write: D2H serialization is synchronous (safe with buffer
                # donation), the disk write overlaps the next epochs
                save_checkpoint(
                    cfg.save_folder, f"ckpt_epoch_{epoch}", state,
                    config=config_lib.config_dict(cfg), epoch=epoch, block=False,
                    extra_meta=policy_meta(),
                )
            if preempt.requested_global():
                # epoch-boundary preemption (the signal landed in the last
                # flush window), decided collectively like the mid-epoch
                # check: persist this epoch unless the scheduled save above
                # already did (name=None skips the write but still drains
                # the async save so its meta stamps), then exit.
                tracing.event(
                    "preempt_exit", track="main:guard", epoch=epoch,
                )
                preempt.emergency_save_and_exit(
                    cfg.save_folder,
                    None if epoch % cfg.save_freq == 0
                    else f"preempt_epoch_{epoch}",
                    state, config_lib.config_dict(cfg), epoch,
                    extra_meta=policy_meta(),
                )
        wait_for_saves()
        save_checkpoint(
            cfg.save_folder, "last", state,
            config=config_lib.config_dict(cfg), epoch=cfg.epochs,
            extra_meta=policy_meta(),
        )
    except BaseException as e:
        exit_exc = e
        raise
    finally:
        # On failure too: stop/flush an active profiler trace (it is most
        # valuable exactly when the epoch loop died), stop the telemetry
        # worker (close never raises — a pending flush error must not mask
        # the real failure), stop the window store's prefetch worker (a
        # pending shadow-buffer upload nobody will read must not stall the
        # exit-75 path), and drain in-flight async checkpoint writes so
        # finished payloads get their meta stamp.
        preempt.uninstall()
        telemetry.close()
        if store is not None:
            store.close()
        tracer.close()
        # the registry is process-wide: a later run of this process (the
        # probe after a pretrain) must not find this run's program
        profiling.clear_step_program()
        tb.close()
        wait_for_saves()
        # observability teardown LAST (after the final wait_for_saves so
        # the checkpoint_commit span lands in the record and the watchdog
        # still watches a wedging drain) — the ordering lives on obs.close.
        # The in-flight exception (if any) classifies the exit for the
        # terminal gauge + run_exit event (utils/guard.py exit-code surface).
        obs.close(exit_code=exit_code_for(exit_exc))
    sync_processes("supcon_run_end")
    return state


def main(argv=None):
    cfg = config_lib.parse_supcon(argv)
    # typed exit codes (docs/RESILIENCE.md): health 3 > flush 2 > NaN 1,
    # preemption 75 via SystemExit — the supervisor's classification input
    exit_with_code(lambda: run(cfg))


if __name__ == "__main__":
    main()
