"""The distributed SimCLR/SupCon train step, as one jitted SPMD program.

TPU-native redesign of the reference hot loop (``main_supcon.py:242-351``):

- the reference runs per-GPU processes that forward a LOCAL half-batch, then
  ``all_gather`` the projection features, re-insert the local grad-carrying
  tensor (hardcoded to ranks 0/1, ``main_supcon.py:268-279``), and rely on DDP to
  mean-reduce gradients. Here the step is written over the logically GLOBAL
  batch; with the batch sharded over the ``data`` mesh axis, XLA materializes the
  feature gather for the O((2B)^2) loss matmul and the gradient reductions as ICI
  collectives — ``lax.all_gather`` is differentiable by construction, so no
  re-insertion trick exists, and it generalizes past 2 devices (fixing reference
  bug: hardcoded world=2);
- SupCon actually works distributed: labels live in the same global program as
  the features (the reference crashes — local labels vs gathered features,
  ``main_supcon.py:287-288`` -> ``losses.py:46-47``);
- feature ordering, normalize-after-gather, the SEC EMA, and the aux-loss linear
  ramps all match the reference step (see inline cites).

Gradient-scale fidelity: in the reference, each rank's backward flows only
through its own feature rows and DDP MEANS gradients over ``ngpu`` ranks, so the
applied gradient is (1/ngpu) of the true global-batch gradient. JAX computes the
exact global gradient, so the loss is multiplied by ``1/grad_div`` (default 2 =
the recipe's ``--ngpu``) before differentiation; weight decay is applied by the
optimizer and is correctly NOT scaled. ``tests/test_distributed.py`` verifies
this equivalence against a simulated per-rank-backward + mean.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from simclr_pytorch_distributed_tpu.models import LinearClassifier
from simclr_pytorch_distributed_tpu.ops.losses import (
    cross_entropy_loss,
    supcon_loss,
)
from simclr_pytorch_distributed_tpu.ops.metrics import (
    embedding_covariance,
    topk_correct,
)
from simclr_pytorch_distributed_tpu.ops.pallas_loss import (
    fused_sharded_supcon_loss,
    fused_supcon_loss,
)
from simclr_pytorch_distributed_tpu.parallel.collectives import ring_supcon_loss
from simclr_pytorch_distributed_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    replicated_sharding,
    state_sharding,
)
from simclr_pytorch_distributed_tpu.train.state import TrainState


# Named scopes of the compiled step: what flax's module paths do not name.
# Every instruction of the program's text then carries one of these (or a
# module path: ``encoder/conv1``, ``encoder/layer2_block1``, ``proj_head``)
# in its ``op_name``, and ``benchmark/scope_reduce.py`` buckets device time
# by it. Metrics are keyed on these strings: rename one and its metric falls
# silent.
SCOPE_DATA = "data"            # resident slice + per-step key fold_in
SCOPE_AUG = "aug"              # two_crop_batch
SCOPE_LOSS = "loss"            # norms, normalize, contrastive / recipe loss
SCOPE_OPTIMIZER = "optimizer"  # tx.update + apply_updates (+ recipe's own)
SCOPE_RING = "ring"            # metric arithmetic, health, the ring write
STEP_SCOPES = (SCOPE_DATA, SCOPE_AUG, SCOPE_LOSS, SCOPE_OPTIMIZER, SCOPE_RING)

# The step's base metric-dict key set (aux + learning_rate), sorted — the
# column order of the device-side metric ring (ops/metrics.MetricRing): the
# jitted writer and the host reader both derive columns from this one tuple,
# so a metric added to ``train_step`` without extending it fails loudly at
# trace time instead of silently shifting columns. Health/probe extensions
# below are opt-in per run; :func:`metric_keys` derives the run's full set,
# and the SAME derivation feeds the writer's trace-time assertion and the
# host reader, so the two sides cannot diverge.
METRIC_KEYS = (
    "learning_rate", "loss", "loss_l2reg", "loss_sec",
    "norm_mean", "norm_var", "record_norm_mean",
)

# Representation-health diagnostics (--health_freq > 0): computed INSIDE the
# jitted update from the loss's own normalized embeddings and the step's
# gradients, written into the same donated ring — zero new per-step D2H. On
# non-health steps (step % health_freq != 0) the columns carry an all-NaN
# sentinel row; host consumers (TB tags, the HealthMonitor, gauges) skip it.
HEALTH_METRIC_KEYS = (
    "health_align",      # mean positive-pair cosine (collapse -> 1.0)
    "health_con_top1",   # contrastive top-1: positive is the argmax contrast
    "health_eff_rank",   # exp-entropy of the d x d embedding covariance
    "health_grad_norm",  # global gradient norm (divergence signal)
    "health_neg_max",    # max negative-pair cosine
    "health_neg_mean",   # mean negative-pair cosine (collapse -> 1.0)
    "health_unif",       # Wang-Isola uniformity, log E exp(-2||z_i-z_j||^2)
)

# Online linear probe (--online_probe on): a detached classifier head trained
# by the same compiled update on stop_gradient encoder features; its loss and
# top-1 (percent, over both views) stream through the ring every step.
ONLINE_PROBE_METRIC_KEYS = ("probe_loss", "probe_top1")


def metric_keys(health: bool = False, online_probe: bool = False, extra=()):
    """The run's full sorted ring-key tuple. The drivers and the step builder
    both call this with the SAME config bits, so a flag mismatch between the
    writer and the TelemetrySession reader fails loudly at trace time
    (MetricRing.write's key check) instead of silently shifting columns.
    ``extra`` is the active recipe's own metric-key tuple
    (``recipe.metric_keys``, e.g. the VICReg term breakdown) — same
    derivation on both sides, same loud-failure contract."""
    keys = METRIC_KEYS + tuple(extra)
    if health:
        keys = keys + HEALTH_METRIC_KEYS
    if online_probe:
        keys = keys + ONLINE_PROBE_METRIC_KEYS
    return tuple(sorted(keys))


def extra_columns(keys) -> tuple:
    """What ``extra`` put into a ring layout ``keys``: the columns beyond the
    step's own and the health and probe families, which are the recipe's and
    the encoder's (``recipes.attach_for_config``). A reader that holds the
    run's ring takes them from here and names none."""
    return tuple(k for k in keys
                 if k not in METRIC_KEYS and not k.startswith(("health_", "probe_")))


def epoch_position(step, steps_per_epoch: int):
    """A step's position within its epoch, derived ON DEVICE from the state's
    global step counter — the resident-data slice index
    (``data/device_store.py``: the step takes the epoch buffer as a
    non-donated arg and slices row ``position`` out of it).

    Valid because every driver maintains ``state.step == (epoch-1) *
    steps_per_epoch + idx`` through ALL control flow: mid-epoch resume
    restores the counter from checkpoint meta, and the NaN-rollback path
    realigns it to the skipped epoch's boundary (train/supcon.py) — so the
    remainder is always the in-epoch index and no extra per-step host scalar
    (which would be an H2D transfer, docs/PERF.md) is needed.
    """
    return jax.lax.rem(step, jnp.int32(steps_per_epoch))


def contrastive_health_metrics(emb: jax.Array, grads) -> dict:
    """The :data:`HEALTH_METRIC_KEYS` diagnostics from one batch.

    ``emb`` is the loss's OWN L2-normalized embedding matrix ``[2B, D]`` in
    the view-major global row layout (rows ``[v1 of all samples; v2 of all
    samples]``, so row ``i``'s positive sits at ``(i + B) % 2B``), passed out
    of ``loss_fn``'s aux under ``stop_gradient`` — nothing here is a second
    forward, and on the dense loss path the similarity matmul is the same
    ``dot(emb, emb^T)`` HLO the loss already builds (XLA CSE-able). Runs only
    on health steps: the caller gates it behind ``lax.cond`` on
    ``step % health_freq``, so non-health steps pay neither the ``O((2B)^2)``
    matmul nor the ``d x d`` eigendecomposition.

    A collapsed representation (all embeddings equal) reads as: align -> 1,
    neg_mean/neg_max -> 1, eff_rank -> 1, unif -> 0 (its maximum), con_top1
    -> chance. A diverging one shows up first in ``grad_norm``.
    """
    n = emb.shape[0]
    b = n // 2
    sim = emb @ emb.T  # [2B, 2B] cosine (rows are unit-norm)
    idx = jnp.arange(n)
    pos_idx = (idx + b) % n
    eye = idx[:, None] == idx[None, :]
    pos = pos_idx[:, None] == idx[None, :]
    neg = ~(eye | pos)
    align = jnp.mean(jnp.sum(emb[:b] * emb[b:], axis=1))
    neg_count = jnp.maximum(jnp.sum(neg.astype(jnp.float32)), 1.0)
    neg_mean = jnp.sum(jnp.where(neg, sim, 0.0)) / neg_count
    neg_max = jnp.max(jnp.where(neg, sim, -jnp.inf))
    # contrastive top-1: is the positive the highest-similarity non-self row?
    top1 = 100.0 * jnp.mean(
        (jnp.argmax(jnp.where(eye, -jnp.inf, sim), axis=1) == pos_idx)
        .astype(jnp.float32)
    )
    # Wang-Isola uniformity with t=2 over non-self pairs; ||z_i - z_j||^2 =
    # 2 - 2*cos for unit rows, so the exponent is bounded in [-8, 0].
    unif = jnp.log(
        jnp.sum(jnp.where(eye, 0.0, jnp.exp(4.0 * sim - 4.0)))
        / (n * (n - 1))
    )
    # effective rank = exp(entropy) of the normalized covariance spectrum
    # (uncentered second moment — ops/metrics.embedding_covariance, the
    # construction the VICReg covariance penalty shares in centered form)
    cov = embedding_covariance(emb)
    eig = jnp.clip(jnp.linalg.eigvalsh(cov), 0.0, None)
    p = eig / jnp.maximum(jnp.sum(eig), 1e-12)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-12)), 0.0))
    return {
        "health_align": align,
        "health_con_top1": top1,
        "health_eff_rank": jnp.exp(entropy),
        "health_grad_norm": optax.global_norm(grads),
        "health_neg_max": neg_max,
        "health_neg_mean": neg_mean,
        "health_unif": unif,
    }


@dataclasses.dataclass(frozen=True)
class OnlineProbe:
    """The online linear probe's static pieces: a ``LinearClassifier`` head
    and its (schedule-free SGD) optimizer. Built once per run by
    :func:`build_online_probe`; the trainable state lives on
    ``TrainState.probe_params`` / ``probe_opt_state``."""

    classifier: Any
    tx: optax.GradientTransformation


def build_online_probe(model_name: str, feat_dim: int, n_cls: int,
                       lr: float, momentum: float = 0.9, seed: int = 0):
    """``(OnlineProbe, probe_params, probe_opt_state)`` for a run.

    The head and recipe mirror the post-hoc probe (train/linear.py:
    ``LinearClassifier`` + SGD momentum, zero weight decay) so the live
    curve estimates the same quantity ``main_linear.py`` measures hours
    later — the documented tolerance between the two is in
    docs/OBSERVABILITY.md. Constant LR: the probe chases a moving encoder,
    so the post-hoc step schedule has nothing to anneal against.
    """
    from simclr_pytorch_distributed_tpu.train.state import make_optimizer

    classifier = LinearClassifier(model_name=model_name, num_classes=n_cls)
    tx = make_optimizer(lr, momentum=momentum, weight_decay=0.0)
    params = classifier.init(
        jax.random.key(seed), jnp.zeros((2, feat_dim))
    )["params"]
    return OnlineProbe(classifier=classifier, tx=tx), params, tx.init(params)


@dataclasses.dataclass(frozen=True)
class RecipeContext:
    """Everything one train step hands a recipe's ``loss`` (recipes/base.py):
    the step's OWN forward products — no recipe re-runs the backbone for the
    online branch — plus the recipe slots. ``feats`` is the unnormalized
    fp32 projection matrix ``[2B, D]`` in the view-major row layout
    (``[v1 of all samples; v2 of all samples]``), ``n_fea`` its L2-normalized
    form (the contrastive/health layout). ``model``/``params``/
    ``batch_stats``/``images`` are for recipes that need a SECOND forward
    through different weights (the BYOL EMA target network)."""

    model: Any
    params: Any
    batch_stats: Any
    images: jax.Array
    labels: jax.Array
    feats: jax.Array
    n_fea: jax.Array
    recipe_params: Any
    recipe_state: Any


@dataclasses.dataclass(frozen=True)
class SupConStepConfig:
    """Static step configuration (mirrors the reference argparse flags)."""

    method: str = "SimCLR"  # --method {SimCLR, SupCon}
    temperature: float = 0.5  # --temp
    base_temperature: float = 0.07  # fixed, losses.py:90
    contrast_mode: str = "all"
    # aux losses (main_supcon.py:76-82, 295-317)
    sec: bool = False
    sec_wei: float = 0.0
    l2reg: bool = False
    l2reg_wei: float = 0.0
    norm_momentum: float = 1.0
    # ramp denominator: epochs * steps_per_epoch (main_supcon.py:311-317)
    epochs: int = 1000
    steps_per_epoch: int = 1
    # DDP gradient-mean fidelity (see module docstring); the recipe's --ngpu.
    grad_div: float = 2.0
    # 'dense' = XLA O(N^2)-materializing path; 'fused' = flash-style Pallas
    # kernel (ops/pallas_loss.py); 'ring' = ppermute-sharded streaming loss
    # (parallel/collectives.py) that keeps anchors sharded over the 'data'
    # axis — O((2B/P)^2) per-device memory for large global batches.
    # Resolved from the config's 'auto' upstream.
    loss_impl: str = "dense"
    # representation-health diagnostics (HEALTH_METRIC_KEYS): computed every
    # health_freq-th step inside a lax.cond (NaN sentinel rows otherwise),
    # written into the same donated metric ring — zero new per-step D2H
    health: bool = False
    health_freq: int = 10
    # online linear probe (ONLINE_PROBE_METRIC_KEYS): make_train_step must
    # then be given the matching OnlineProbe spec, and the state must carry
    # probe_params/probe_opt_state
    online_probe: bool = False


def two_view_forward(
    model, params, batch_stats, images: jax.Array, *,
    train: bool = True, with_features: bool = False, with_aux: bool = False,
):
    """Forward both views through the encoder+head as ONE batch.

    ``images`` is ``[B, 2, H, W, C]``. Views are flattened view-major —
    rows ``[v1 of all samples; v2 of all samples]`` — the same global layout the
    reference assembles post-gather (``main_supcon.py:276-279``). Both views
    share one BN batch, matching the reference's ``cat([v1, v2])`` forward
    (``main_supcon.py:256,266``).

    ``with_features=True`` routes through ``forward_with_features`` (one
    backbone pass, models/heads.py) and the FIRST return element becomes the
    ``(projection, encoder_features)`` pair — the online probe's input
    without a second encoder forward. Default callers see the unchanged
    2-tuple.

    ``with_aux=True`` (train mode) appends a third element: the collection
    ``aux`` that the encoder sowed (models/token_encoder.py: its auxiliary
    loss and its metric-ring columns). A ResNet sows nothing and is never
    asked.
    """
    B = images.shape[0]
    with jax.named_scope(SCOPE_AUG):  # the views' layout, not the encoder's time
        flat = jnp.transpose(images, (1, 0, 2, 3, 4)).reshape(
            (2 * B,) + images.shape[2:]
        )
    method = type(model).forward_with_features if with_features else None
    if train:
        feats, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            flat, train=True, method=method,
            mutable=["batch_stats", "aux"] if with_aux else ["batch_stats"],
        )
        if with_aux:
            return feats, mutated["batch_stats"], mutated["aux"]
        return feats, mutated["batch_stats"]
    feats = model.apply(
        {"params": params, "batch_stats": batch_stats}, flat, train=False,
        method=method,
    )
    return feats, batch_stats


def contrastive_loss_terms(
    cfg: SupConStepConfig, mesh, fused_on_mesh: bool, n_fea: jax.Array, labels
):
    """The contrastive loss term over the normalized view-major ``[2B, D]``
    embedding rows — the pre-recipe step's loss head, extracted VERBATIM so
    the inline (``recipe=None``) control path and the supcon/simclr recipe
    (recipes/supcon.py) share one implementation; the recipe dispatch around
    it is proven bitwise-neutral driver-level (tests/test_recipes.py,
    docs/PARITY.md). ``labels`` is the SupCon label vector or ``None`` for
    SimCLR (the caller resolves ``cfg.method``)."""
    B = n_fea.shape[0] // 2
    # stack views back to [B_global, 2, D] with f1 = all view-1 rows
    # (main_supcon.py:285-286)
    n_features = jnp.stack([n_fea[:B], n_fea[B:]], axis=1)
    loss_labels = labels
    if cfg.loss_impl in ("fused", "ring") and cfg.contrast_mode != "all":
        raise ValueError(
            f"loss_impl={cfg.loss_impl!r} implements contrast_mode='all' "
            f"only; got {cfg.contrast_mode!r} — use loss_impl='dense'"
        )
    if cfg.loss_impl == "ring":
        # anchors stay sharded over 'data'; n_fea is already the view-major
        # global row layout the ring expects ([v1 rows; v2 rows]).
        def _ring(rows, lab):
            return ring_supcon_loss(
                rows, lab, axis_name=DATA_AXIS,
                temperature=cfg.temperature,
                base_temperature=cfg.base_temperature, n_views=2,
            )

        if loss_labels is None:
            contrastive = shard_map(
                lambda r: _ring(r, None),
                mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(),
            )(n_fea)
        else:
            contrastive = shard_map(
                _ring, mesh=mesh,
                in_specs=(P(DATA_AXIS), P()), out_specs=P(),
            )(n_fea, loss_labels)
    elif fused_on_mesh:
        # same row layout and shard_map plumbing as the ring path; the
        # kernel needs check_vma=False (interpret-mode Pallas cannot type
        # kernel-internal constants) — its custom VJP compensates for the
        # per-shard cotangent shares (ops/pallas_loss.py).
        def _fs(rows, lab):
            return fused_sharded_supcon_loss(
                rows, lab, axis_name=DATA_AXIS,
                temperature=cfg.temperature,
                base_temperature=cfg.base_temperature, n_views=2,
                interpret=jax.default_backend() != "tpu",
            )

        if loss_labels is None:
            contrastive = shard_map(
                lambda r: _fs(r, None), mesh=mesh,
                in_specs=P(DATA_AXIS), out_specs=P(), check_vma=False,
            )(n_fea)
        else:
            contrastive = shard_map(
                _fs, mesh=mesh,
                in_specs=(P(DATA_AXIS), P()), out_specs=P(),
                check_vma=False,
            )(n_fea, loss_labels)
    elif cfg.loss_impl == "fused":
        contrastive = fused_supcon_loss(
            n_features, labels=loss_labels,
            temperature=cfg.temperature, base_temperature=cfg.base_temperature,
            # Mosaic compiles only on TPU; anywhere else (CPU tests) the
            # kernel runs under the Pallas interpreter. What rules out a
            # silent interpret run on the chip is chip_smoke.py's platform
            # check plus the loss_impl banner, not an option here.
            interpret=jax.default_backend() != "tpu",
        )
    else:
        contrastive = supcon_loss(
            n_features, labels=loss_labels,
            temperature=cfg.temperature, base_temperature=cfg.base_temperature,
            contrast_mode=cfg.contrast_mode,
        )
    return contrastive


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    schedule: Callable,
    cfg: SupConStepConfig,
    mesh=None,
    probe: Optional[OnlineProbe] = None,
    recipe=None,
) -> Callable:
    """Build the pure train step: (state, images[B,2,H,W,C], labels[B]) -> (state, metrics).

    ``mesh`` is required only for ``loss_impl='ring'`` (the shard_map needs an
    explicit mesh; dense/fused run as plain HLO that GSPMD partitions).

    ``probe`` (an :class:`OnlineProbe`, required iff ``cfg.online_probe``)
    adds the detached online-probe update: the classifier trains on
    ``stop_gradient`` encoder features from the SAME backbone forward, so the
    encoder/head/optimizer math is bit-identical probe-on vs probe-off
    (tests/test_health.py proves it bitwise) and the probe costs one
    ``[2B, feat_dim] x [feat_dim, n_cls]`` matmul pair per step.

    ``recipe`` (a recipes/ Recipe) swaps the loss head and its extra slots:
    the recipe's ``loss`` runs inside this same jitted update on the step's
    own forward (``RecipeContext``), a trainable recipe's predictor rides
    ``state.recipe_params`` under its own optimizer chain, and its post-step
    transition (BYOL EMA, queue rotation) lands in ``state.recipe_state`` —
    all in ONE compiled program, so every recipe inherits the dispatch-only
    hot loop. ``None`` keeps the pre-recipe inline contrastive step (bench,
    the dryrun modes, and the bitwise-neutrality control arm — the
    contrastive term itself is shared via :func:`contrastive_loss_terms`).
    """
    if cfg.loss_impl == "ring" and mesh is None:
        raise ValueError("loss_impl='ring' needs the mesh passed to make_train_step")
    if (probe is not None) != cfg.online_probe:
        raise ValueError(
            f"online_probe={cfg.online_probe} but probe spec "
            f"{'missing' if probe is None else 'given'} — the step config "
            "and the OnlineProbe must be built together"
        )
    # an encoder with auxiliary terms (models/token_encoder.py) streams its
    # own columns: recipes.attach_for_config has put them after the recipe's
    # own; a step built without a recipe object has the encoder's alone
    encoder_extra = tuple(getattr(model, "aux_metric_keys", ()))
    recipe_extra = encoder_extra if recipe is None else tuple(recipe.metric_keys)
    recipe_trainable = recipe is not None and recipe.trainable
    expected_keys = metric_keys(
        health=cfg.health, online_probe=cfg.online_probe, extra=recipe_extra
    )
    if cfg.health and cfg.health_freq < 1:
        raise ValueError(f"health_freq must be >= 1, got {cfg.health_freq}")
    # 'fused' on a multi-device mesh routes through the shard_map-sharded
    # kernel (ops/pallas_loss.py fused_sharded_supcon_loss): anchors stay
    # sharded over 'data', the contrast side is all-gathered, and the logits
    # tiles never leave VMEM. A bare pallas_call has no GSPMD partitioning
    # rule, so without this the kernel would run fully replicated.
    fused_on_mesh = (
        cfg.loss_impl == "fused" and mesh is not None and mesh.size > 1
    )

    def loss_fn(params, recipe_params, state: TrainState, images, labels):
        probe_feats = None
        how = {"with_features": True} if probe is not None else {}
        if encoder_extra:
            how["with_aux"] = True
        feats, new_batch_stats, *sown = two_view_forward(
            model, params, state.batch_stats, images, train=True, **how
        )
        if probe is not None:
            feats, enc_feats = feats
            # the probe's whole detachment contract: gradients CANNOT flow
            # from the classifier back into the encoder
            probe_feats = jax.lax.stop_gradient(enc_feats.astype(jnp.float32))
        # everything between the head's output and the scalar loss is the
        # loss's device time: norms, the normalize, the contrastive (or the
        # recipe's) term with its custom-VJP backward, the aux ramps and
        # the DDP gradient scale
        with jax.named_scope(SCOPE_LOSS):
            feats = feats.astype(jnp.float32)

            # feature-norm statistics on UNNORMALIZED embeddings (main_supcon.py:298-301)
            norms = jnp.linalg.norm(feats, axis=1)
            norm_mean = jnp.mean(norms)
            norm_var = jnp.mean(jnp.square(norms - norm_mean))

            # SEC EMA: update-then-use, seeded with the first batch's mean
            # (main_supcon.py:304-307; momentum 1.0 degenerates to the batch mean)
            norm_mean_sg = jax.lax.stop_gradient(norm_mean)
            record = jnp.where(
                state.step == 0,
                norm_mean_sg,
                (1.0 - cfg.norm_momentum) * state.record_norm_mean
                + cfg.norm_momentum * norm_mean_sg,
            )
            loss_sec = jnp.mean(jnp.square(norms - record))
            loss_l2reg = jnp.mean(jnp.square(norms))

            # normalize AFTER the (logical) gather (main_supcon.py:283)
            n_fea = feats / jnp.linalg.norm(feats, axis=1, keepdims=True)

            recipe_aux = {}
            if recipe is None:
                # the pre-recipe inline path (bitwise control arm; bench/dryruns)
                if cfg.method not in ("SupCon", "SimCLR"):
                    raise ValueError(
                        f"contrastive method not supported: {cfg.method}"
                    )
                loss_labels = labels if cfg.method == "SupCon" else None
                contrastive = contrastive_loss_terms(
                    cfg, mesh, fused_on_mesh, n_fea, loss_labels
                )
            else:
                ctx = RecipeContext(
                    model=model, params=params, batch_stats=state.batch_stats,
                    images=images, labels=labels, feats=feats, n_fea=n_fea,
                    recipe_params=recipe_params, recipe_state=state.recipe_state,
                )
                contrastive, recipe_aux = recipe.loss(cfg, mesh, fused_on_mesh, ctx)

            # linear-ramped aux terms (main_supcon.py:311-317)
            ramp = state.step / (cfg.epochs * cfg.steps_per_epoch)
            loss = contrastive
            if cfg.sec:
                loss = loss + cfg.sec_wei * ramp * loss_sec
            if cfg.l2reg:
                loss = loss + cfg.l2reg_wei * ramp * loss_l2reg
            encoder_metrics = {}
            if encoder_extra:
                # the encoder's own terms, weighted where they arise: the
                # expert layers' balance term and the indexers' KL
                encoder_loss, encoder_metrics = model.read_aux(sown[0])
                loss = loss + encoder_loss
            # grad-scale fidelity: DDP means over ngpu ranks (module docstring)
            scaled_loss = loss / cfg.grad_div

        aux = {
            "loss": loss,  # the reported (unscaled) loss, main_supcon.py:320
            "norm_mean": norm_mean,
            "norm_var": norm_var,
            "record_norm_mean": record,
            "loss_sec": loss_sec,
            "loss_l2reg": loss_l2reg,
        }
        # recipe extras: metric terms (recipe.metric_keys) + the detached
        # rotation payload ("recipe_embeddings", queue recipes)
        aux.update(recipe_aux)
        aux.update(encoder_metrics)
        if cfg.health:
            # the loss's OWN normalized, view-major embedding rows — the
            # health diagnostics' input, detached so aux plumbing cannot
            # perturb the gradient
            aux["embeddings"] = jax.lax.stop_gradient(n_fea)
        if probe is not None:
            aux["probe_feats"] = probe_feats
        return scaled_loss, (aux, new_batch_stats)

    def probe_update(state: TrainState, probe_feats, labels):
        """One detached classifier step on the stop_gradient encoder
        features of BOTH views (labels tiled view-major to match)."""
        labels2 = jnp.concatenate([labels, labels])

        def probe_loss_fn(pp):
            logits = probe.classifier.apply({"params": pp}, probe_feats)
            return cross_entropy_loss(logits, labels2), logits

        (ploss, logits), pgrads = jax.value_and_grad(
            probe_loss_fn, has_aux=True
        )(state.probe_params)
        pupdates, new_popt = probe.tx.update(
            pgrads, state.probe_opt_state, state.probe_params
        )
        new_pparams = optax.apply_updates(state.probe_params, pupdates)
        top1 = topk_correct(logits, labels2, ks=(1,))[1]
        pmetrics = {
            "probe_loss": ploss,
            "probe_top1": 100.0 * top1.astype(jnp.float32) / labels2.shape[0],
        }
        return new_pparams, new_popt, pmetrics

    def train_step(
        state: TrainState, images: jax.Array, labels: jax.Array
    ) -> Tuple[TrainState, dict]:
        if recipe_trainable:
            # joint gradient: the recipe's predictor trains WITH the encoder
            # (BYOL/SimSiam gradients reach the backbone only through the
            # predictor path), each under its own optimizer chain
            (grads, rgrads), (aux, new_batch_stats) = jax.grad(
                loss_fn, argnums=(0, 1), has_aux=True
            )(state.params, state.recipe_params, state, images, labels)
        else:
            grads, (aux, new_batch_stats) = jax.grad(loss_fn, has_aux=True)(
                state.params,
                None if recipe is None else state.recipe_params,
                state, images, labels,
            )
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, new_opt_state = tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        with jax.named_scope(SCOPE_RING):
            metrics = dict(
                aux, learning_rate=jnp.asarray(schedule(state.step))
            )
        metrics.pop("embeddings", None)
        metrics.pop("probe_feats", None)
        metrics.pop("recipe_embeddings", None)
        replace_kwargs = {}
        if recipe_trainable:
            with jax.named_scope(SCOPE_OPTIMIZER):
                rupdates, new_ropt = recipe.tx.update(
                    rgrads, state.recipe_opt_state, state.recipe_params
                )
                replace_kwargs.update(
                    recipe_params=optax.apply_updates(
                        state.recipe_params, rupdates
                    ),
                    recipe_opt_state=new_ropt,
                )
        if recipe is not None and state.recipe_state is not None:
            # the recipe's post-step state transition (BYOL EMA toward the
            # freshly updated online params; queue rotation with the batch's
            # detached embeddings) — still inside this one compiled program
            replace_kwargs["recipe_state"] = recipe.post_step(
                state.recipe_state, new_params=new_params, aux=aux
            )
        if cfg.health:
            # lax.cond, not where: the false branch must SKIP the O((2B)^2)
            # similarity matmul and the d x d eigendecomposition at runtime,
            # not just mask their results — non-health steps pay nothing
            with jax.named_scope(SCOPE_RING):
                metrics.update(jax.lax.cond(
                    state.step % cfg.health_freq == 0,
                    lambda ops: contrastive_health_metrics(*ops),
                    lambda ops: {
                        k: jnp.full((), jnp.nan, jnp.float32)
                        for k in HEALTH_METRIC_KEYS
                    },
                    (aux["embeddings"], grads),
                ))
        if probe is not None:
            new_pparams, new_popt, pmetrics = probe_update(
                state, aux["probe_feats"], labels
            )
            metrics.update(pmetrics)
            replace_kwargs.update(
                probe_params=new_pparams, probe_opt_state=new_popt
            )
        assert tuple(sorted(metrics)) == expected_keys, sorted(metrics)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_batch_stats,
            opt_state=new_opt_state,
            record_norm_mean=aux["record_norm_mean"],
            **replace_kwargs,
        )
        return new_state, metrics

    return train_step


def make_sharded_train_step(
    model,
    tx: optax.GradientTransformation,
    schedule: Callable,
    cfg: SupConStepConfig,
    mesh,
    state_shape: Optional[Any] = None,
    donate: bool = True,
    recipe=None,
) -> Callable:
    """jit the train step over the mesh: state replicated, batch data-sharded.

    Under GSPMD this single program IS the distributed algorithm: XLA inserts the
    feature all-gather for the loss matmul and a gradient reduce over ICI —
    the TPU-native replacement for NCCL all_gather + DDP bucketed all-reduce.
    """
    step = make_train_step(model, tx, schedule, cfg, mesh=mesh, recipe=recipe)
    repl = replicated_sharding(mesh)

    state_sh = (
        state_sharding(mesh, state_shape) if state_shape is not None else repl
    )
    in_shardings = (
        state_sh,
        batch_sharding(mesh, 5),  # images [B, 2, H, W, C]
        batch_sharding(mesh, 1),  # labels [B]
    )
    return jax.jit(
        step,
        in_shardings=in_shardings,
        out_shardings=(state_sh, repl),
        donate_argnums=(0,) if donate else (),
    )
