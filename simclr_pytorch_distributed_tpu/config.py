"""Configuration: the reference's argparse surface, dataclass-backed.

Flag names, defaults, and DERIVED fields match the reference parsers —
``main_supcon.py:22-152`` (pretrain), ``main_linear.py:21-116`` (probe), and the
CE baseline (whose parser was lost in the reference fork; rebuilt from the
probe's). The derivations that matter for recipe parity are kept bit-identical:

- ``model_name`` run-string encoding (``main_supcon.py:109-117``);
- auto-warmup when ``batch_size > 256`` (``:120-121``);
- closed-form ``warmup_to`` (``:124-131``, via ops/schedules.warmup_to_value);
- timestamped tb/save folder layout (``:133-142``), created on the main process.

TPU-native additions (not in the reference): ``--bf16`` compute dtype,
``--resume`` full-state resume, ``--model_parallel`` mesh axis size,
``--seed``, ``--dataset synthetic``, ``--workdir``. The reference's ``--ngpu``
flag is kept but means "DDP gradient-scale equivalence divisor" (see
train/supcon_step.py) — actual parallelism comes from the mesh, not a flag.
"""

from __future__ import annotations

import argparse
import dataclasses
import datetime
import os
from typing import Optional, Tuple

from simclr_pytorch_distributed_tpu.ops.schedules import warmup_to_value
from simclr_pytorch_distributed_tpu.parallel.mesh import is_main_process


@dataclasses.dataclass
class SupConConfig:
    # cadence
    print_freq: int = 10
    save_freq: int = 20
    batch_size: int = 256
    num_workers: int = 16  # CLI-parity only: augmentation runs on device
    epochs: int = 1000
    # optimization (main_supcon.py:37-47)
    learning_rate: float = 0.5
    lr_decay_epochs: Tuple[int, ...] = (700, 800, 900)
    lr_decay_rate: float = 0.1
    weight_decay: float = 1e-4
    momentum: float = 0.9
    # model / dataset (main_supcon.py:49-56)
    model: str = "resnet50"
    dataset: str = "cifar10"  # {cifar10, cifar100, path, synthetic, synthetic_hard, synthetic_hard32}
    mean: Optional[str] = None
    std: Optional[str] = None
    data_folder: Optional[str] = None
    size: int = 32
    # 'path' datasets: host-side storage resolution (0 = 2*size); the device
    # RandomResizedCrop samples from this resolution (data/folder.py)
    store_size: int = 0
    # 'path' datasets: decoded trees above this go through the on-disk memmap
    # cache instead of RAM (data/folder.py; bounded host RSS for big trees)
    mmap_threshold_mb: int = 1024
    # method (main_supcon.py:58-64)
    method: str = "SimCLR"  # {SupCon, SimCLR}
    temp: float = 0.5
    # other settings (main_supcon.py:66-88)
    cosine: bool = False
    syncBN: bool = False
    warm: bool = False
    trial: str = "0"
    sec: bool = False
    sec_wei: float = 0.0
    norm_momentum: float = 1.0
    l2reg: bool = False
    l2reg_wei: float = 0.0
    ckpt: str = ""
    # grad-scale equivalence divisor (reference --ngpu default 2); also
    # accepts 'auto' = resolve to the mesh's data-parallel size at startup
    # (resolve_ngpu). A non-auto mismatch prints a startup banner naming the
    # effective-LR consequence (ngpu_mismatch_banner).
    ngpu: object = 2
    # head (reference hardcodes SupConResNet defaults, resnet_big.py:161)
    head: str = "mlp"
    feat_dim: int = 128
    # --- TPU-native additions ---
    # fetch CIFAR if absent (the reference's torchvision download=True,
    # main_supcon.py:181-188); process-0-gated in the drivers
    download: bool = True
    bf16: bool = False
    resume: str = ""
    model_parallel: int = 1
    seed: int = 0
    workdir: str = "./work_space"
    # NOTE: per-iter TB scalars follow --print_freq (the reference logs every
    # iter, which forces a device sync per step)
    # contrastive-loss implementation: 'auto' picks the fused Pallas kernel on
    # a single TPU chip, the dense XLA path otherwise (ops/pallas_loss.py);
    # 'ring' streams contrast blocks around the data axis with ppermute
    # (parallel/collectives.py) for large-global-batch memory scaling
    loss_impl: str = "auto"
    # retired (PR 30): the encoder has one conv path, XLA's. The field and
    # the flag stay because benchmark/configs/*.json pass '--conv_impl auto';
    # nothing reads it (ROADMAP D14)
    conv_impl: str = "auto"
    # 'sgd' is the published recipe (util.py:79-84); 'lars' for the
    # large-global-batch configs (SimCLR ImageNet bs=4096, BASELINE configs[4])
    optimizer: str = "sgd"
    # jax.profiler trace capture (SURVEY.md §5 tracing row; reference has none)
    trace_dir: str = ""
    trace_start_step: int = 10
    trace_steps: int = 10
    # abort + emergency-checkpoint on NaN/Inf loss (utils/guard.py)
    nan_guard: bool = True
    # what to DO about a non-finite loss (utils/guard.py FailurePolicy):
    # 'abort' dies after the crash_epoch_N save; 'rollback' restores the
    # epoch-boundary backup, skips the poisoned epoch with the LR halved,
    # and continues (bounded by guard.MAX_ROLLBACKS)
    nan_policy: str = "abort"
    # per-block activation rematerialization: trades recompute FLOPs for HBM
    # so bigger per-chip batches fit (identical numerics; models/resnet.py)
    remat: bool = False
    # where the per-window metric flush (D2H + NaN check + meters + TB) runs:
    # 'async' = background telemetry thread, zero sync on the hot loop (NaN
    # detection at most one print_freq window late — utils/telemetry.py);
    # 'sync' = inline on the dispatch thread (the pre-ring semantics)
    telemetry: str = "async"
    # where training batches live (data/device_store.py): 'device' keeps the
    # uint8 dataset HBM-resident (one index upload + compiled shuffle-gather
    # per epoch; the hot loop is dispatch-only — no per-step H2D); 'window'
    # streams a double-buffered window of permutation-ordered batches (one
    # H2D per window — datasets that don't fit HBM, incl. memmap-backed
    # folder trees); 'host' is the per-step device_put loop; 'auto' walks
    # the device -> window -> host ladder against the budget. Batch
    # composition is bit-identical in every placement.
    data_placement: str = "auto"
    # windowed placement: batches per resident window; HBM cost is 2x one
    # window (the training window + the prefetched shadow buffer)
    data_window_batches: int = 32
    # override the computed per-device placement budget, in MB (0 = 0.4x
    # free memory_stats, with a fixed 4 GB fallback where stats are absent
    # — untunable exactly where it matters without this)
    device_budget_mb: int = 0
    # --- observability (docs/OBSERVABILITY.md) ---
    # representation-health diagnostics (train/supcon_step.py
    # HEALTH_METRIC_KEYS): alignment / uniformity / contrastive top-1 /
    # negative-similarity stats / gradient norm / embedding effective rank,
    # computed inside the jitted update every health_freq-th step and shipped
    # through the existing metric ring (zero new per-step D2H); 0 = off
    health_freq: int = 10
    # what a collapse/divergence verdict does (utils/guard.HealthMonitor):
    # 'warn' logs + emits health_alarm flight-recorder events; 'abort' exits
    # with RepresentationHealthError (collective, like the NaN exit; NEVER
    # rolled back — see docs/RESILIENCE.md precedence note)
    health_policy: str = "warn"
    # online linear probe (train/supcon_step.py): a detached classifier head
    # on stop_gradient encoder features trained by the same compiled update,
    # so probe top-1 streams live through the ring instead of waiting for
    # the post-hoc main_linear.py pass; checkpointed in its own payload
    online_probe: str = "off"
    probe_lr: float = 0.1
    # --- SSL recipes (simclr_pytorch_distributed_tpu/recipes/) ---
    # which loss head rides the substrate: 'auto' = the --method-matching
    # contrastive recipe (the pre-recipe behavior); 'supcon'/'simclr' force
    # the method; 'byol'/'simsiam'/'vicreg' are the negative-free /
    # redundancy-reduction siblings (validate_recipe resolves + checks the
    # flag interactions at parse time)
    recipe: str = "auto"
    # MoCo-style device-side negative queue (recipes/supcon.py): K past
    # embeddings contrasted as extra negatives, rotated in-program — simclr
    # only, K a multiple of 2*batch_size, dense loss path; 0 = off
    moco_queue: int = 0
    # EMA momentum of the slow branch: byol's target network AND the moco
    # queue's key encoder (tau/m; slow = tau*slow + (1-tau)*online per step)
    ema_momentum: float = 0.996
    # byol: 'none' ablates the predictor — the known-collapsing form that
    # must trip the eff-rank collapse alarm (the recipes' injection arm)
    byol_predictor: str = "mlp"
    # byol/simsiam predictor hidden width (models/heads.PredictorHead)
    predictor_hidden: int = 512
    # vicreg term weights (ops/losses.vicreg_loss; paper defaults 25/25/1)
    vicreg_sim_coeff: float = 25.0
    vicreg_std_coeff: float = 25.0
    vicreg_cov_coeff: float = 1.0
    # flight recorder (utils/tracing.py): host-boundary span/event log ->
    # <run_dir>/events.jsonl + Chrome-trace trace.json; zero device
    # syncs/transfers added (asserted mechanically in tier-1)
    flight_recorder: str = "on"
    # stall watchdog: if the flush boundary hasn't advanced in this many
    # seconds, dump all thread stacks + a recorder snapshot to the run dir
    # (a silent collective deadlock becomes an attributable artifact);
    # 0 = off. Must comfortably exceed the first-step compile.
    watchdog_secs: float = 0.0
    # Prometheus /metrics sidecar (utils/prom.py TrainerGauges): step,
    # last-boundary age, in-flight windows, pending checkpoint saves;
    # 0 = off. Binds loopback by default — exposing an unauthenticated
    # endpoint on all interfaces is an explicit choice (--metrics_host).
    metrics_port: int = 0
    metrics_host: str = "127.0.0.1"
    # derived (finalize_supcon)
    warm_epochs: int = 10
    warmup_from: float = 0.01
    warmup_to: float = 0.0
    model_name: str = ""
    tb_folder: str = ""
    save_folder: str = ""


def _add_bool_flag(parser, name, default=False, help=""):
    parser.add_argument(f"--{name}", action="store_true", default=default, help=help)


def _parse_bool(s: str) -> bool:
    v = s.lower()
    if v in ("1", "true", "yes", "on"):
        return True
    if v in ("0", "false", "no", "off"):
        return False
    raise argparse.ArgumentTypeError(f"expected a boolean, got {s!r}")


def ngpu_arg(s: str):
    """--ngpu accepts the reference's int OR 'auto' (mesh-resolved)."""
    if s.strip().lower() == "auto":
        return "auto"
    try:
        v = int(s)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--ngpu expects a positive integer or 'auto', got {s!r}"
        ) from None
    if v <= 0:
        # it becomes the gradient DIVISOR: 0 divides by zero, negatives
        # flip the update direction — reject at parse, not mid-startup
        raise argparse.ArgumentTypeError(f"--ngpu must be positive, got {v}")
    return v


def positive_int_arg(name: str):
    """argparse type for flags that must be >= 1 (the --ngpu convention:
    reject at parse, not mid-startup — these feed divisors and byte
    budgets where 0/negatives fail far from the flag)."""

    def parse(s: str) -> int:
        try:
            v = int(s)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"--{name} expects a positive integer, got {s!r}"
            ) from None
        if v <= 0:
            raise argparse.ArgumentTypeError(
                f"--{name} must be positive, got {v}"
            )
        return v

    return parse


def resolve_ngpu(ngpu, data_parallel: int) -> int:
    """The effective grad divisor: ``'auto'`` -> the mesh's data-parallel
    size; integers (or int-like strings from restored config dicts) pass
    through unchanged."""
    if isinstance(ngpu, str) and ngpu.strip().lower() == "auto":
        return int(data_parallel)
    v = int(ngpu)
    if v <= 0:  # programmatic configs bypass ngpu_arg
        raise ValueError(f"ngpu must be positive, got {v}")
    return v


def ngpu_mismatch_banner(ngpu: int, data_parallel: int, learning_rate: float) -> str:
    """Startup banner for an explicit --ngpu that differs from the mesh.

    The step divides the exact global-batch gradient by ``ngpu`` (DDP
    grad-mean fidelity with the reference's ``ngpu``-GPU runs,
    train/supcon_step.py). When the mesh's data-parallel size differs, that
    divisor no longer matches the hardware, which silently rescales the
    effective learning rate — worth a banner, not a log line lost in startup
    noise (VERDICT round 5 #8).
    """
    eff = learning_rate * data_parallel / ngpu
    bar = "=" * 72
    return (
        f"\n{bar}\n"
        f"  --ngpu {ngpu} but the mesh is data-parallel over {data_parallel} "
        f"device(s).\n"
        f"  Gradients are divided by {ngpu} (recipe fidelity with the "
        f"reference's {ngpu}-GPU runs): relative to mesh-matched scaling the "
        f"applied update is {data_parallel}/{ngpu} = "
        f"{data_parallel / ngpu:.3g}x, i.e. an EFFECTIVE learning rate of "
        f"~{eff:.4g} instead of the configured {learning_rate:g}.\n"
        f"  Pass --ngpu auto (or --ngpu {data_parallel}) to scale with this "
        f"mesh instead.\n"
        f"{bar}"
    )


def supcon_parser() -> argparse.ArgumentParser:
    d = SupConConfig()
    p = argparse.ArgumentParser("argument for training")
    p.add_argument("--print_freq", type=int, default=d.print_freq)
    p.add_argument("--save_freq", type=int, default=d.save_freq)
    p.add_argument("--batch_size", type=int, default=d.batch_size)
    p.add_argument("--num_workers", type=int, default=d.num_workers)
    p.add_argument("--epochs", type=int, default=d.epochs)
    p.add_argument("--learning_rate", type=float, default=d.learning_rate)
    p.add_argument("--lr_decay_epochs", type=str, default="700,800,900")
    p.add_argument("--lr_decay_rate", type=float, default=d.lr_decay_rate)
    p.add_argument("--weight_decay", type=float, default=d.weight_decay)
    p.add_argument("--momentum", type=float, default=d.momentum)
    p.add_argument("--model", type=str, default=d.model)
    p.add_argument("--dataset", type=str, default=d.dataset,
                   choices=["cifar10", "cifar100", "path", "synthetic", "synthetic_hard", "synthetic_hard32"])
    p.add_argument("--mean", type=str, default=None,
                   help="mean of dataset in path in form of str tuple")
    p.add_argument("--std", type=str, default=None)
    p.add_argument("--data_folder", type=str, default=None)
    p.add_argument("--no_download", dest="download", action="store_false",
                   default=True, help="never fetch CIFAR over the network")
    p.add_argument("--size", type=int, default=d.size)
    p.add_argument("--store_size", type=int, default=d.store_size,
                   help="path datasets: stored resolution (0 = 2*size)")
    p.add_argument("--mmap_threshold_mb", type=int, default=d.mmap_threshold_mb,
                   help="path datasets: decode to an on-disk memmap above this size")
    p.add_argument("--method", type=str, default=d.method, choices=["SupCon", "SimCLR"])
    p.add_argument("--temp", type=float, default=d.temp)
    _add_bool_flag(p, "cosine")
    _add_bool_flag(p, "syncBN")
    _add_bool_flag(p, "warm")
    p.add_argument("--trial", type=str, default=d.trial)
    _add_bool_flag(p, "sec")
    p.add_argument("--sec_wei", type=float, default=d.sec_wei)
    p.add_argument("--norm_momentum", type=float, default=d.norm_momentum)
    _add_bool_flag(p, "l2reg")
    p.add_argument("--l2reg_wei", type=float, default=d.l2reg_wei)
    p.add_argument("--ckpt", type=str, default=d.ckpt)
    p.add_argument("--ngpu", type=ngpu_arg, default=d.ngpu,
                   help="DDP grad-mean divisor (reference fidelity), or "
                        "'auto' = the mesh's data-parallel size")
    p.add_argument("--head", type=str, default=d.head, choices=["mlp", "linear"])
    p.add_argument("--feat_dim", type=int, default=d.feat_dim)
    _add_bool_flag(p, "bf16")
    p.add_argument("--resume", type=str, default=d.resume)
    p.add_argument("--model_parallel", type=int, default=d.model_parallel)
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--workdir", type=str, default=d.workdir)
    p.add_argument("--loss_impl", type=str, default=d.loss_impl,
                   choices=["auto", "dense", "fused", "ring"])
    p.add_argument("--conv_impl", type=str, default=d.conv_impl,
                   choices=["auto", "xla"],
                   help="retired: the encoder has one conv path, XLA's, and "
                        "both values mean it (the whole-block Pallas kernels "
                        "behind the former 'pallas' were never timed on the "
                        "chip, and its compiler refused five of the twelve "
                        "at the launcher's geometry); still accepted because "
                        "the benchmark's configurations pass it")
    p.add_argument("--optimizer", type=str, default=d.optimizer,
                   choices=["sgd", "lars"],
                   help="lars: layer-adaptive scaling for large global batches")
    _add_bool_flag(p, "remat", help="remat residual blocks (HBM for recompute)")
    p.add_argument("--nan_guard", type=_parse_bool,
                   default=d.nan_guard, help="abort + checkpoint on NaN loss")
    p.add_argument("--nan_policy", type=str, default=d.nan_policy,
                   choices=["abort", "rollback"],
                   help="on NaN loss: die after the crash save (typed exit "
                        "code 1, docs/RESILIENCE.md — what the supervisor "
                        "keys on), or restore the epoch backup, halve the "
                        "LR, and continue")
    p.add_argument("--health_freq", type=nonnegative_int_arg("health_freq"),
                   default=d.health_freq,
                   help="compute the representation-health diagnostics "
                        "(alignment/uniformity/contrastive top-1/negative "
                        "sims/grad norm/effective rank) inside the jitted "
                        "update every Nth step, shipped through the metric "
                        "ring (no new per-step transfers); 0 = off")
    p.add_argument("--health_policy", type=str, default=d.health_policy,
                   choices=["warn", "abort"],
                   help="on a windowed collapse/divergence verdict: log + "
                        "flight-recorder event, or exit with the typed "
                        "RepresentationHealthError (exit code 3 — the "
                        "supervisor gives up rather than retrying, since "
                        "collapse lives in the weights; never rolled back)")
    p.add_argument("--recipe", type=str, default=d.recipe,
                   choices=["auto", "supcon", "simclr", "byol", "simsiam",
                            "vicreg"],
                   help="SSL loss head (recipes/): 'auto' = the --method-"
                        "matching contrastive recipe; supcon/simclr force "
                        "the method; byol = predictor + EMA target; simsiam "
                        "= predictor + stop-gradient; vicreg = invariance/"
                        "variance/covariance")
    p.add_argument("--moco_queue", type=nonnegative_int_arg("moco_queue"),
                   default=d.moco_queue,
                   help="MoCo-style negative queue: an EMA key encoder + a "
                        "device-side ring of K past keys as extra NT-Xent "
                        "negatives, rotated in-program (simclr recipe only; "
                        "K a multiple of 2*batch_size; dense loss path); "
                        "0=off")
    p.add_argument("--ema_momentum", type=float, default=d.ema_momentum,
                   help="EMA momentum in [0, 1) of the slow branch: byol's "
                        "target network / the moco queue's key encoder")
    p.add_argument("--byol_predictor", type=str, default=d.byol_predictor,
                   choices=["mlp", "none"],
                   help="byol predictor head; 'none' ablates it (the known-"
                        "collapsing form — the collapse-injection arm)")
    p.add_argument("--predictor_hidden",
                   type=positive_int_arg("predictor_hidden"),
                   default=d.predictor_hidden,
                   help="byol/simsiam predictor MLP hidden width")
    p.add_argument("--vicreg_sim_coeff", type=float, default=d.vicreg_sim_coeff,
                   help="vicreg invariance weight (paper: 25)")
    p.add_argument("--vicreg_std_coeff", type=float, default=d.vicreg_std_coeff,
                   help="vicreg variance-hinge weight (paper: 25)")
    p.add_argument("--vicreg_cov_coeff", type=float, default=d.vicreg_cov_coeff,
                   help="vicreg covariance weight (paper: 1)")
    p.add_argument("--online_probe", type=str, default=d.online_probe,
                   choices=["on", "off"],
                   help="train a detached linear probe on stop_gradient "
                        "encoder features inside the same compiled update; "
                        "probe loss/top-1 stream live through the ring")
    p.add_argument("--probe_lr", type=float, default=d.probe_lr,
                   help="online probe SGD learning rate (constant; the "
                        "probe chases a moving encoder)")
    _add_shared_runtime_flags(p, d)
    _add_observability_flags(p, d)
    return p


def nonnegative_int_arg(name: str):
    """argparse type for cadence flags where 0 means 'off' but negatives are
    nonsense (the positive_int_arg convention, with 0 admitted)."""

    def parse(s: str) -> int:
        try:
            v = int(s)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"--{name} expects a non-negative integer, got {s!r}"
            ) from None
        if v < 0:
            raise argparse.ArgumentTypeError(
                f"--{name} must be >= 0 (0 = off), got {v}"
            )
        return v

    return parse


def _add_shared_runtime_flags(p: argparse.ArgumentParser, d) -> None:
    """The shared runtime surface (telemetry/data-placement/profiling):
    ONE registry serving all three trainers' parsers.

    These flags mean the same thing on every stage, so they must parse the
    same way everywhere — previously three hand-synced copies, now the one
    definition the invariant linter's flag-consistency rule
    (analysis/rule_registry.py SHARED_RUNTIME_FLAGS) verifies by USAGE:
    registering one of these inline in a parser again is a lint finding,
    and the dataclass defaults (``d.<field>``) must agree across
    SupConConfig/LinearConfig.
    """
    p.add_argument("--telemetry", type=str, default=d.telemetry,
                   choices=["async", "sync"],
                   help="metric flush: background thread (zero sync on the "
                        "hot loop; NaN detection <=1 window late) or inline")
    p.add_argument("--data_placement", type=str, default=d.data_placement,
                   choices=["host", "device", "window", "auto"],
                   help="training batches: 'device' = HBM-resident epoch "
                        "buffer; 'window' = double-buffered streaming "
                        "window, one H2D per window (fits datasets HBM "
                        "can't hold, incl. memmap-backed trees); 'auto' "
                        "walks the device->window->host ladder; 'host' = "
                        "per-step H2D")
    p.add_argument("--data_window_batches",
                   type=positive_int_arg("data_window_batches"),
                   default=d.data_window_batches,
                   help="windowed placement: batches per resident window "
                        "(HBM cost = 2x one window: training + shadow)")
    p.add_argument("--device_budget_mb",
                   type=positive_int_arg("device_budget_mb"),
                   default=d.device_budget_mb,
                   help="override the per-device placement budget in MB "
                        "(default: 0.4x free memory_stats, 4 GB fallback "
                        "where the backend reports no stats)")
    p.add_argument("--trace_dir", type=str, default=d.trace_dir,
                   help="capture a jax.profiler trace into this dir")
    p.add_argument("--trace_start_step", type=int, default=d.trace_start_step)
    p.add_argument("--trace_steps", type=int, default=d.trace_steps)


def _add_observability_flags(p: argparse.ArgumentParser, d) -> None:
    """The shared observability surface (docs/OBSERVABILITY.md): identical
    on all three trainers, like the runtime flags above."""
    p.add_argument("--flight_recorder", type=str, default=d.flight_recorder,
                   choices=["on", "off"],
                   help="host-boundary span/event recorder -> "
                        "<run_dir>/events.jsonl + trace.json "
                        "(utils/tracing.py); adds no device syncs")
    p.add_argument("--watchdog_secs", type=float, default=d.watchdog_secs,
                   help="stall watchdog: dump all thread stacks + a "
                        "recorder snapshot when the flush boundary stalls "
                        "this long (0 = off; set well above the first-step "
                        "compile)")
    p.add_argument("--metrics_port", type=int, default=d.metrics_port,
                   help="Prometheus /metrics sidecar port (step, "
                        "last-boundary age, in-flight windows, pending "
                        "saves); 0 = off")
    p.add_argument("--metrics_host", type=str, default=d.metrics_host,
                   help="sidecar bind address (default loopback; set "
                        "0.0.0.0 to let a remote Prometheus scrape)")


def validate_data_placement(dataset: str, data_placement: str) -> None:
    """Parse-time check of --data_placement interactions.

    ``path`` trees can decode into an on-disk memmap (data/folder.py above
    ``--mmap_threshold_mb``), which device residency refuses — whether THIS
    tree does is only known after the decode, so an explicit ``device``
    request is rejected up front rather than failing deep in setup; ``auto``
    resolves against the decoded array (and walks the ladder with a
    banner). Explicit ``window`` passes: the window store streams from a
    memmap by construction (each window's gather reads only its own rows),
    so the post-decode representation cannot invalidate the request.
    """
    if data_placement == "device" and dataset == "path":
        raise ValueError(
            "--data_placement device is not accepted with --dataset path: "
            "folder datasets may decode to an on-disk memmap "
            "(--mmap_threshold_mb), which cannot be made device-resident — "
            "use --data_placement auto (decides from the decoded size, "
            "falls back to host with a banner) or host"
        )


def validate_model(cfg: SupConConfig) -> None:
    """Parse-time check of --model against the encoders there are
    (models/resnet.MODEL_DICT), and of --size against a token encoder's
    patches: both would otherwise fail inside the first trace."""
    from simclr_pytorch_distributed_tpu.models import MODEL_DICT, TOKEN_ENCODERS

    if cfg.model not in MODEL_DICT:
        raise ValueError(
            f"--model {cfg.model!r} is no encoder; choose from {sorted(MODEL_DICT)}"
        )
    spec = TOKEN_ENCODERS.get(cfg.model)
    if spec is not None and cfg.size % spec.patch:
        raise ValueError(
            f"--model {cfg.model} cuts views into {spec.patch}x{spec.patch} "
            f"patches; --size {cfg.size} is no multiple"
        )


def impl_resolution_banner(
    flag: str, requested: str, resolved: str, reason: str
) -> str:
    """One-line startup banner for an impl-resolution ladder
    (``--loss_impl`` — the data_placement ladder convention): names the
    RESOLVED implementation and WHY, so a silent degradation (unsupported
    geometry, non-TPU backend) is discoverable from the log instead of only
    from the resolution code."""
    if requested == resolved:
        return f"[{flag}] '{resolved}': {reason}"
    return f"[{flag}] requested '{requested}' -> resolved '{resolved}': {reason}"


def validate_recipe(cfg: SupConConfig) -> None:
    """Resolve ``--recipe auto`` and check the recipe flag interactions at
    PARSE time (the --ngpu convention: these feed tree geometry and loss
    kernels where a bad value fails far from the flag).

    Mutates ``cfg.recipe`` to the concrete name and, for the contrastive
    recipes, forces ``cfg.method`` to match (``--recipe`` is the outer
    selector; a method the recipe contradicts is an error only for the
    label-free recipes, where an explicit ``--method SupCon`` would be
    silently meaningless).
    """
    if cfg.recipe == "auto":
        cfg.recipe = "supcon" if cfg.method == "SupCon" else "simclr"
    elif cfg.recipe == "supcon":
        # forcing the method here is unambiguous: --method defaults to
        # SimCLR, so a SimCLR value cannot be distinguished from "not given"
        cfg.method = "SupCon"
    elif cfg.recipe == "simclr":
        if cfg.method == "SupCon":
            # SupCon is NOT the --method default, so this is an explicit,
            # contradictory ask — dropping the labels silently would train
            # unsupervised while the user believes otherwise
            raise ValueError(
                "--recipe simclr contradicts --method SupCon (the recipe "
                "is label-free NT-Xent) — drop --method, or use "
                "--recipe supcon"
            )
        cfg.method = "SimCLR"
    else:  # byol / simsiam / vicreg: label-free
        if cfg.method == "SupCon":
            raise ValueError(
                f"--recipe {cfg.recipe} is label-free; --method SupCon has "
                "no effect there — drop the flag (or use --recipe supcon)"
            )
    if cfg.moco_queue:
        if cfg.recipe != "simclr":
            raise ValueError(
                f"--moco_queue holds NEGATIVES only, which --recipe "
                f"{cfg.recipe} cannot use "
                + ("(supervised positives may sit in the queue)"
                   if cfg.recipe == "supcon" else "(no contrastive term)")
                + " — it requires --recipe simclr"
            )
        if cfg.moco_queue % (2 * cfg.batch_size) != 0:
            raise ValueError(
                f"--moco_queue {cfg.moco_queue} must be a multiple of "
                f"2*batch_size ({2 * cfg.batch_size}): the in-program ring "
                "write (dynamic_update_slice) clamps at the edge instead of "
                "wrapping, so partial-batch rotations would corrupt the queue"
            )
        if cfg.loss_impl in ("fused", "ring"):
            raise ValueError(
                f"--moco_queue extends the contrast side past the fixed "
                f"2B geometry the {cfg.loss_impl!r} kernel tiles — use "
                "--loss_impl dense (or auto, which resolves to dense)"
            )
    if not 0.0 <= cfg.ema_momentum < 1.0:
        raise ValueError(
            f"--ema_momentum must be in [0, 1), got {cfg.ema_momentum}"
        )
    for name in ("vicreg_sim_coeff", "vicreg_std_coeff", "vicreg_cov_coeff"):
        if getattr(cfg, name) < 0:
            raise ValueError(
                f"--{name} must be >= 0, got {getattr(cfg, name)}"
            )


def parse_supcon(argv=None) -> SupConConfig:
    ns = supcon_parser().parse_args(argv)
    kwargs = vars(ns)
    kwargs["lr_decay_epochs"] = tuple(int(x) for x in kwargs["lr_decay_epochs"].split(","))
    cfg = SupConConfig(**kwargs)
    return finalize_supcon(cfg)


def finalize_supcon(cfg: SupConConfig, make_dirs: bool = True) -> SupConConfig:
    """Derived fields, replicating main_supcon.py:92-150."""
    validate_data_placement(cfg.dataset, cfg.data_placement)
    validate_recipe(cfg)
    validate_model(cfg)
    if cfg.dataset == "path":
        assert cfg.data_folder is not None and cfg.mean is not None and cfg.std is not None
    if cfg.data_folder is None:
        cfg.data_folder = "./datasets/"

    cfg.model_name = (
        f"{cfg.method}_{cfg.dataset}_{cfg.model}_lr_{cfg.learning_rate}"
        f"_decay_{cfg.weight_decay}_bsz_{cfg.batch_size}_temp_{cfg.temp}_trial_{cfg.trial}"
    )
    if cfg.cosine:
        cfg.model_name = f"{cfg.model_name}_cosine"
    if cfg.sec:
        cfg.model_name = f"{cfg.model_name}_sec"
    if cfg.batch_size > 256:
        cfg.warm = True
    if cfg.warm:
        cfg.model_name = f"{cfg.model_name}_warm"
        cfg.warmup_from = 0.01
        cfg.warm_epochs = 10
        cfg.warmup_to = warmup_to_value(
            cfg.learning_rate, cfg.lr_decay_rate, cfg.warm_epochs, cfg.epochs, cfg.cosine
        )

    now_time = datetime.datetime.now().strftime("%m%d_%H%M")
    prefix = f"{cfg.dataset}_{now_time}_"
    model_path = os.path.join(cfg.workdir, f"{cfg.dataset}_models")
    tb_path = os.path.join(cfg.workdir, f"{cfg.dataset}_tensorboard")
    cfg.tb_folder = os.path.join(tb_path, prefix + cfg.model_name)
    cfg.save_folder = os.path.join(model_path, prefix + cfg.model_name)
    if make_dirs and is_main_process():
        os.makedirs(cfg.tb_folder, exist_ok=True)
        os.makedirs(cfg.save_folder, exist_ok=True)
    return cfg


@dataclasses.dataclass
class LinearConfig:
    """Probe config (main_linear.py:21-116); also serves the CE baseline."""

    print_freq: int = 10
    save_freq: int = 10
    batch_size: int = 512
    num_workers: int = 16
    epochs: int = 100
    learning_rate: float = 0.1
    lr_decay_epochs: Tuple[int, ...] = (60, 75, 90)
    lr_decay_rate: float = 0.2
    weight_decay: float = 0.0
    momentum: float = 0.9
    model: str = "resnet50"
    dataset: str = "cifar10"  # {cifar10, cifar100, synthetic, synthetic_hard, synthetic_hard32}
    cosine: bool = False
    warm: bool = False
    # CE trainer only: per-device vs synchronized BN, same conditional the
    # reference's pretrain applies (main_supcon.py:223-224); default off =
    # per-device statistics. The probe ignores it (frozen eval-mode encoder).
    syncBN: bool = False
    download: bool = True  # fetch CIFAR if absent (torchvision parity)
    ckpt: str = ""
    # TPU-native additions
    # CE trainer only: full-state (step-granular) resume, same semantics as
    # the pretrain --resume; the probe ignores it (no full-state checkpoints)
    resume: str = ""
    data_folder: str = "./datasets/"
    size: int = 32
    val_batch_size: int = 256  # main_ce.py:64-66
    bf16: bool = False
    seed: int = 0
    workdir: str = "./work_space"
    trial: str = "0"
    telemetry: str = "async"  # same semantics as the pretrain flag
    data_placement: str = "auto"  # same semantics as the pretrain flag
    data_window_batches: int = 32  # same semantics as the pretrain flag
    device_budget_mb: int = 0  # same semantics as the pretrain flag
    # jax.profiler trace capture — previously pretrain-only, so the probe/CE
    # stages could not capture an xplane window (utils/profiling.StepTracer)
    trace_dir: str = ""
    trace_start_step: int = 10
    trace_steps: int = 10
    flight_recorder: str = "on"  # same semantics as the pretrain flag
    watchdog_secs: float = 0.0  # same semantics as the pretrain flag
    metrics_port: int = 0  # same semantics as the pretrain flag
    metrics_host: str = "127.0.0.1"  # same semantics as the pretrain flag
    # derived
    n_cls: int = 10
    warm_epochs: int = 10
    warmup_from: float = 0.01
    warmup_to: float = 0.0
    model_name: str = ""
    tb_folder: str = ""
    save_folder: str = ""


def linear_parser(ce: bool = False) -> argparse.ArgumentParser:
    d = LinearConfig()
    p = argparse.ArgumentParser("argument for training")
    p.add_argument("--print_freq", type=int, default=d.print_freq)
    p.add_argument("--save_freq", type=int, default=d.save_freq)
    p.add_argument("--batch_size", type=int, default=d.batch_size)
    p.add_argument("--num_workers", type=int, default=d.num_workers)
    p.add_argument("--epochs", type=int, default=d.epochs)
    p.add_argument("--learning_rate", type=float, default=d.learning_rate)
    p.add_argument("--lr_decay_epochs", type=str, default="60,75,90")
    p.add_argument("--lr_decay_rate", type=float, default=d.lr_decay_rate)
    p.add_argument("--weight_decay", type=float, default=d.weight_decay)
    p.add_argument("--momentum", type=float, default=d.momentum)
    p.add_argument("--model", type=str, default=d.model)
    p.add_argument("--dataset", type=str, default=d.dataset,
                   choices=["cifar10", "cifar100", "synthetic", "synthetic_hard", "synthetic_hard32"])
    _add_bool_flag(p, "cosine")
    _add_bool_flag(p, "warm")
    if ce:
        _add_bool_flag(p, "syncBN")
        p.add_argument("--resume", type=str, default=d.resume,
                       help="checkpoint (or run dir) to resume from")
    if not ce:
        p.add_argument("--ckpt", type=str, default=d.ckpt,
                       help="path to pre-trained model checkpoint dir")
        p.add_argument("--resume", type=str, default=d.resume,
                       help="accepted for the exit-75 launcher contract "
                            "(re-run the same command with --resume); the "
                            "probe keeps no full-state checkpoints, so it "
                            "retrains from scratch")
    p.add_argument("--data_folder", type=str, default=d.data_folder)
    p.add_argument("--no_download", dest="download", action="store_false",
                   default=True, help="never fetch CIFAR over the network")
    p.add_argument("--val_batch_size", type=int, default=d.val_batch_size)
    _add_bool_flag(p, "bf16")
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--workdir", type=str, default=d.workdir)
    p.add_argument("--trial", type=str, default=d.trial)
    _add_shared_runtime_flags(p, d)
    _add_observability_flags(p, d)
    return p


def parse_linear(argv=None, ce: bool = False) -> LinearConfig:
    ns = linear_parser(ce=ce).parse_args(argv)
    kwargs = vars(ns)
    kwargs["lr_decay_epochs"] = tuple(int(x) for x in kwargs["lr_decay_epochs"].split(","))
    cfg = LinearConfig(**kwargs)
    return finalize_linear(cfg, prefix="ce_" if ce else "classifier_")


def finalize_linear(
    cfg: LinearConfig, prefix: str = "classifier_", make_dirs: bool = True
) -> LinearConfig:
    """Derived fields, replicating main_linear.py:65-114."""
    cfg.model_name = (
        f"{cfg.dataset}_{cfg.model}_lr_{cfg.learning_rate}"
        f"_decay_{cfg.weight_decay}_bsz_{cfg.batch_size}"
    )
    if cfg.cosine:
        cfg.model_name = f"{cfg.model_name}_cosine"
    if cfg.warm:
        cfg.model_name = f"{cfg.model_name}_warm"
        cfg.warmup_from = 0.01
        cfg.warm_epochs = 10
        cfg.warmup_to = warmup_to_value(
            cfg.learning_rate, cfg.lr_decay_rate, cfg.warm_epochs, cfg.epochs, cfg.cosine
        )
    cfg.n_cls = {"cifar10": 10, "cifar100": 100, "synthetic": 10, "synthetic_hard": 10,
                 "synthetic_hard32": 32}[cfg.dataset]

    now_time = datetime.datetime.now().strftime("%m%d_%H%M")
    run = prefix + now_time + "_"
    cfg.tb_folder = os.path.join(cfg.workdir, f"{cfg.dataset}_tensorboard", run + cfg.model_name)
    cfg.save_folder = os.path.join(cfg.workdir, f"{cfg.dataset}_models", run + cfg.model_name)
    if make_dirs and is_main_process():
        os.makedirs(cfg.tb_folder, exist_ok=True)
        os.makedirs(cfg.save_folder, exist_ok=True)
    return cfg


def config_dict(cfg) -> dict:
    """JSON-safe config for checkpoint metadata (unlike the reference, which
    pickles the whole namespace incl. a live tensor, util.py:89-94)."""
    out = {}
    for k, v in dataclasses.asdict(cfg).items():
        out[k] = list(v) if isinstance(v, tuple) else v
    return out
