"""recipes/ — the pluggable SSL-recipe subsystem (``--recipe``).

The substrate (two-view pipeline, device/window stores, zero-sync metric
ring, online probe, health monitor, flight recorder, checkpoint/ratchet
discipline) is recipe-agnostic in everything but the loss head; this package
supplies the heads. Four recipes ship (docs/README recipe matrix):

- ``supcon`` / ``simclr`` — the original contrastive behavior behind the
  interface (recipes/supcon.py; bitwise-equal to the pre-refactor step,
  docs/PARITY.md), optionally with a MoCo-style device-side negative queue
  (``--moco_queue``);
- ``byol`` — predictor head + EMA target network (recipes/byol.py);
- ``simsiam`` — predictor + stop-gradient, no EMA (recipes/simsiam.py);
- ``vicreg`` — invariance/variance/covariance (recipes/vicreg.py).

:func:`build_recipe` turns a finalized ``SupConConfig`` into the recipe
object the step builder closes over; :func:`attach_recipe_slots` installs
the recipe's initial TrainState slots (a no-op for slot-free recipes, so
those state trees stay exactly the pre-recipe ones).
"""

from __future__ import annotations

import dataclasses

import jax

from simclr_pytorch_distributed_tpu.recipes.base import (  # noqa: F401
    Recipe,
    RecipeContext,
)
from simclr_pytorch_distributed_tpu.recipes.byol import BYOLRecipe
from simclr_pytorch_distributed_tpu.recipes.simsiam import SimSiamRecipe
from simclr_pytorch_distributed_tpu.recipes.supcon import ContrastiveRecipe
from simclr_pytorch_distributed_tpu.recipes.vicreg import VICRegRecipe

# the --recipe surface (config.py validates against this; 'auto' resolves to
# the --method-matching contrastive recipe)
RECIPE_NAMES = ("supcon", "simclr", "byol", "simsiam", "vicreg")

# name -> implementing class: the ONE place metric-key/class knowledge is
# looked up by name, so a recipe that grows metric columns is picked up by
# every name-based consumer (EXTRA_TB_TAGS, train_one_epoch's fallback key
# derivation) without editing this module
_RECIPE_CLASSES = {
    "supcon": ContrastiveRecipe,
    "simclr": ContrastiveRecipe,
    "byol": BYOLRecipe,
    "simsiam": SimSiamRecipe,
    "vicreg": VICRegRecipe,
}


def recipe_metric_keys(name: str) -> tuple:
    """The extra ring columns recipe ``name`` streams (for readers that
    have a config but no recipe object) — read off the class's own
    ``metric_keys`` declaration, never re-encoded by name."""
    cls = _RECIPE_CLASSES.get(name)
    return tuple(cls.metric_keys) if cls is not None else ()


# every recipe metric key any recipe can stream — the TB-tag map and
# offline readers key off this (train/supcon.py EXTRA_TB_TAGS)
ALL_RECIPE_METRIC_KEYS = tuple(sorted(
    set().union(*(recipe_metric_keys(n) for n in RECIPE_NAMES))
))


def build_recipe(cfg, schedule=None) -> Recipe:
    """The recipe object for a finalized ``SupConConfig``.

    ``schedule`` (the run's LR schedule) feeds the trainable recipes'
    predictor optimizer — the same ``make_optimizer`` chain as the encoder
    (momentum/weight-decay/optimizer flags shared), so a predictor trains
    under the run's hyperparameters. Falls back to the constant
    ``cfg.learning_rate`` when no schedule is given (bench, tests).
    """
    from simclr_pytorch_distributed_tpu.models.heads import PredictorHead
    from simclr_pytorch_distributed_tpu.train.state import make_optimizer

    name = cfg.recipe
    if name not in RECIPE_NAMES:
        raise ValueError(
            f"unknown recipe {name!r} (choose from {RECIPE_NAMES}; was "
            "config.finalize_supcon run?)"
        )
    if name in ("supcon", "simclr"):
        return ContrastiveRecipe(
            name=name, moco_queue=cfg.moco_queue, feat_dim=cfg.feat_dim,
            queue_seed=cfg.seed, ema_momentum=cfg.ema_momentum,
        )
    if name == "vicreg":
        return VICRegRecipe(
            sim_coeff=cfg.vicreg_sim_coeff, std_coeff=cfg.vicreg_std_coeff,
            cov_coeff=cfg.vicreg_cov_coeff,
        )

    def predictor_tx():
        return make_optimizer(
            schedule if schedule is not None else cfg.learning_rate,
            momentum=cfg.momentum, weight_decay=cfg.weight_decay,
            optimizer=cfg.optimizer,
        )

    predictor = PredictorHead(
        dim_hidden=cfg.predictor_hidden, dim_out=cfg.feat_dim
    )
    if name == "byol":
        ablated = cfg.byol_predictor == "none"
        return BYOLRecipe(
            predictor=None if ablated else predictor,
            ema_momentum=cfg.ema_momentum,
            tx=None if ablated else predictor_tx(),
        )
    return SimSiamRecipe(predictor=predictor, tx=predictor_tx())


def attach_recipe_slots(recipe: Recipe, model, state, rng):
    """Install the recipe's initial TrainState slots (predictor params +
    optimizer state, EMA target, queue ring). A strict no-op for slot-free
    recipes — the returned state IS the input state, so trees, checkpoints,
    and jit cache keys are untouched (the probe-off contract)."""
    rp, ro, rs = recipe.init_slots(
        model, state.params, state.batch_stats, rng
    )
    if rp is None and ro is None and rs is None:
        return state
    return state.replace(
        recipe_params=rp, recipe_opt_state=ro, recipe_state=rs
    )


def attach_for_config(cfg, model, state, schedule=None):
    """``(state_with_slots, recipe)`` in one call — the drivers' and bench's
    shared entry point (the ``device_store.make_store`` convention). The rng
    is derived from ``cfg.seed + 2`` (the probe uses ``seed``, the data key
    ``seed + 1``).

    This is where a run's extra ring columns are decided: the recipe's own
    and, after them, those that ``model``'s encoder says it sows
    (``model.aux_metric_keys``; none for a ResNet). The step builder and
    every reader take ``recipe.metric_keys`` as returned here."""
    recipe = build_recipe(cfg, schedule=schedule)
    encoder_keys = tuple(getattr(model, "aux_metric_keys", ()))
    if encoder_keys:
        recipe = dataclasses.replace(
            recipe, metric_keys=tuple(recipe.metric_keys) + encoder_keys)
    state = attach_recipe_slots(
        recipe, model, state, jax.random.key(cfg.seed + 2)
    )
    return state, recipe
