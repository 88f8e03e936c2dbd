#!/usr/bin/env python
"""Pretrain throughput benchmark: imgs/sec/chip on the recipe workload.

Runs the fused SimCLR train step (device-side two-crop augmentation + ResNet-50
forward/backward + global NT-Xent + SGD) at the published recipe config
(bs=256 global, 32x32, temp 0.5, SyncBN) on the available chips and prints ONE
JSON line. The reference publishes no throughput numbers (BASELINE.json
``published`` is empty), so the committed baseline is this REPO's own recorded
headline (``REPO_BASELINES``, the round-5 chip measurement): ``vs_baseline``
reports against it for stages that have one (1.0 otherwise), and
``scripts/ratchet.py`` gates on 95% of it so a perf regression fails CI like
an accuracy regression does (VERDICT round 5 #6).

Honesty guard: on the round-1 bench machine ``jax.block_until_ready``
returned BEFORE the computation finished, which made its numbers physically
impossible (implied MFU ~600%+). A host readback of a *computed scalar*
(``float(metrics["loss"])``) cannot exist until the step ran, so each timing
window ends with one (``chip_smoke.py`` re-times both syncs on whatever
machine it runs on). On top of that, every window's
throughput is cross-checked against the program's XLA FLOP count and the
chip's peak: windows whose implied MFU exceeds ``CREDIBLE_MFU`` are discarded
as clock glitches, and the headline is the **median** of the credible windows —
never a best-of-N, which selects exactly the most-wrong samples.
"""

import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

# Peak dense bf16 throughput assumed for MFU accounting, by device kind.
# v5e ("TPU v5 lite"): 197 TFLOP/s bf16 (public spec). A device kind missing
# from the tables is an error (``peak_for``), never an assumed default.
PEAK_TFLOPS_BY_KIND = {
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v4": 275.0,
    "TPU v5p": 459.0,
    "TPU v6e": 918.0,
}
# Peak HBM bandwidth (GB/s) by device kind, public specs: v5e 819, v4 1228,
# v5p 2765, v6e 1640. Used for the roofline: implied_hbm_util next to
# implied_mfu says WHICH ceiling the workload is actually against.
PEAK_HBM_GBPS_BY_KIND = {
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v4": 1228.0,
    "TPU v5p": 2765.0,
    "TPU v6e": 1640.0,
}


def peak_for(table: dict, device_kind: str) -> float:
    if device_kind not in table:
        raise SystemExit(
            f"bench.py has no peak for device kind {device_kind!r} "
            f"(known: {sorted(table)}): add its public spec, with source, "
            "to the table"
        )
    return table[device_kind]


CREDIBLE_MFU = 0.70  # anything above this on this workload is a clock glitch

# Committed per-stage throughput baselines (imgs/s/chip) — the repo's own
# recorded headline numbers. ``vs_baseline`` reports
# against these; scripts/ratchet.py's bench gate fails below
# RATCHET_BENCH_FRACTION of the stage baseline (chip-noise margin from the
# BENCH_r05 window spread). Update ONLY when a new chip round records a new
# headline (and say so in docs/PERF.md).
REPO_BASELINES = {
    # round-5 headline: 4,066.5 imgs/s/chip at 63.0 ms/step on the v5e bench
    # chip (BENCH_r05.json, recipe config, fused loss, bf16)
    "pretrain": 4066.5,
}
# The chip the baselines were recorded on (jax device_kind spelling, see
# docs/evidence/bench_*_r5.json). The numbers are chip-specific: the ratchet
# bench gate only enforces the bar when the bench ran on this kind.
REPO_BASELINE_DEVICE_KIND = "TPU v5 lite"
RATCHET_BENCH_FRACTION = 0.95


def vs_baseline_for(stage: str, per_chip: float) -> float:
    """per-chip throughput vs the recorded repo baseline (1.0 = no record)."""
    baseline = REPO_BASELINES.get(stage)
    if not baseline or per_chip <= 0:
        return 1.0
    return round(per_chip / baseline, 4)


def _compile_with_flops(update, *example_args):
    """AOT-compile the update once; return (callable, FLOPs/step, bytes/step).

    Both counts come from XLA's own cost analysis of the PER-DEVICE module.
    Reusing the compiled executable avoids paying the big XLA compile twice
    (once for cost analysis, once for the jit cache). A compile or
    cost-analysis failure raises: a bench without a FLOP count cannot
    cross-check its own clock."""
    compiled = update.lower(*example_args).compile()
    cost = compiled.cost_analysis()
    return compiled, float(cost["flops"]), float(cost["bytes accessed"])


# window length for the --data_placement window bench arm: the driver
# default is 32, but the bench buffer only has to exercise the windowed
# slice program (epoch_position % W), not a realistic window economy
BENCH_WINDOW_BATCHES = 8


def _setup_pretrain(mesh, batch, size, data_placement="host",
                    recipe="simclr", moco_queue=0):
    """The headline workload: fused SimCLR pretrain step (recipe config).

    ``data_placement='device'`` benches the resident-store step instead
    (data/device_store.py): the jitted update takes the full-epoch
    ``[steps, batch, ...]`` buffers and slices its own batch at
    ``state.step % steps_per_epoch`` — the same program the drivers run
    under ``--data_placement device``, so the slice's cost (if any) is
    measured with the existing methodology. ``'window'`` benches the
    WINDOWED step program the same way: a ``[BENCH_WINDOW_BATCHES, batch,
    ...]`` resident window sliced at ``epoch_position % W`` — so the
    windowed hot loop shows up in ``vs_baseline`` and the scaling story
    next to the host and resident arms. Note bench's 'host' arm is
    already transfer-free (the same example batch every step — the
    resident-batch FLOOR); these arms isolate the in-program slice, while
    ``scripts/resident_ab.py`` / ``scripts/window_ab.py`` measure the
    driver-loop transfer removal.

    ``recipe`` benches the other SSL loss heads on the SAME methodology
    (recipes/: byol = predictor + EMA target second forward, simsiam =
    predictor + stop-gradient, vicreg = var/cov terms, supcon = labeled
    contrastive; ``moco_queue`` adds the device-side negative ring to the
    simclr arm). ``vs_baseline`` stays pinned to the recorded supcon-family
    pretrain headline for every recipe arm, so a recipe's overhead (the EMA
    update, the queue rotation, the extra target forward) is MEASURED
    against the same floor, not guessed.
    """
    from simclr_pytorch_distributed_tpu import config as config_lib
    from simclr_pytorch_distributed_tpu import recipes as recipes_lib
    from simclr_pytorch_distributed_tpu.models import SupConResNet
    from simclr_pytorch_distributed_tpu.ops.augment import AugmentConfig
    from simclr_pytorch_distributed_tpu.ops.schedules import make_lr_schedule
    from simclr_pytorch_distributed_tpu.parallel.mesh import shard_host_batch
    from simclr_pytorch_distributed_tpu.train.state import (
        create_train_state,
        make_optimizer,
    )
    from simclr_pytorch_distributed_tpu.train.supcon import (
        make_fused_update,
        resolve_loss_impl,
    )
    from simclr_pytorch_distributed_tpu.train.supcon_step import SupConStepConfig

    steps_per_epoch = 50000 // batch
    # bf16 compute on the MXU; fp32 params/BN stats/loss
    model = SupConResNet(
        model_name="resnet50", head="mlp", feat_dim=128, dtype=jnp.bfloat16,
    )
    schedule = make_lr_schedule(
        learning_rate=0.5, epochs=100, steps_per_epoch=steps_per_epoch, cosine=True
    )
    tx = make_optimizer(schedule, momentum=0.9, weight_decay=1e-4)
    state = create_train_state(
        model, tx, jax.random.key(0), jnp.zeros((2, size, size, 3))
    )
    # the recipe arm rides the same update builder as the drivers; the
    # config is finalize-validated so bench rejects the same bad flag
    # combinations the trainers do (queue geometry, supcon+queue, ...)
    recipe_cfg = config_lib.SupConConfig(
        recipe=recipe, moco_queue=moco_queue, batch_size=batch,
        learning_rate=0.5, loss_impl="auto",
    )
    config_lib.validate_recipe(recipe_cfg)
    loss_impl = resolve_loss_impl(
        "auto", batch, len(jax.devices()), moco_queue=moco_queue
    )
    step_cfg = SupConStepConfig(
        method=recipe_cfg.method, temperature=0.5, epochs=100,
        steps_per_epoch=steps_per_epoch, grad_div=2.0, loss_impl=loss_impl,
    )
    state, recipe_obj = recipes_lib.attach_for_config(
        recipe_cfg, model, state, schedule=schedule
    )
    update = make_fused_update(
        model, tx, schedule, step_cfg, AugmentConfig(size=size), mesh, state,
        resident=data_placement != "host",
        window_batches=(
            BENCH_WINDOW_BATCHES if data_placement == "window" else None
        ),
        recipe=recipe_obj,
    )

    rng = np.random.default_rng(0)
    if data_placement != "host":
        # the drivers' resident layout: shuffled batches on device, batch
        # dim sharded (parallel/mesh.epoch_buffer_sharding) — a full epoch
        # for the resident store, one window for the window store
        from simclr_pytorch_distributed_tpu.parallel.mesh import (
            epoch_buffer_sharding,
        )

        lead = (
            BENCH_WINDOW_BATCHES if data_placement == "window"
            else steps_per_epoch
        )
        images = rng.integers(
            0, 256, size=(lead, batch, size, size, 3), dtype=np.uint8,
        )
        labels = rng.integers(0, 10, size=(lead, batch)).astype(np.int32)
        sh_images = jax.device_put(images, epoch_buffer_sharding(mesh, 5))
        sh_labels = jax.device_put(labels, epoch_buffer_sharding(mesh, 2))
    else:
        images = rng.integers(0, 256, size=(batch, size, size, 3), dtype=np.uint8)
        labels = rng.integers(0, 10, size=(batch,)).astype(np.int32)
        sh_images, sh_labels = shard_host_batch((images, labels), mesh)

    config = (
        f"{recipe} rn50 cifar-recipe bf16 fused-aug bsz{batch} "
        f"loss={loss_impl}"
        + ("" if not moco_queue else f" moco_queue={moco_queue}")
        + ("" if data_placement == "host" else f" data={data_placement}")
    )
    return update, sh_images, sh_labels, state, "pretrain", config


def _setup_linear(mesh, batch, size):
    """The probe workload (reference run_linear.sh): frozen eval-mode rn50
    encoder forward + classifier update, RRC+flip aug, recipe bs=256."""
    from simclr_pytorch_distributed_tpu import config as config_lib
    from simclr_pytorch_distributed_tpu.ops.augment import AugmentConfig
    from simclr_pytorch_distributed_tpu.parallel.mesh import shard_host_batch
    from simclr_pytorch_distributed_tpu.train.linear import (
        build_probe,
        make_probe_steps,
        stats_for,
    )

    cfg = config_lib.LinearConfig(
        model="resnet50", dataset="cifar10", batch_size=batch,
        learning_rate=5.0, bf16=True, n_cls=10,
    )
    from simclr_pytorch_distributed_tpu.models import SupConResNet

    encoder = SupConResNet(model_name="resnet50", dtype=jnp.bfloat16)
    enc_vars = encoder.init(
        jax.random.key(0), jnp.zeros((2, size, size, 3)), train=False
    )
    _, classifier, _, tx, state, encode = build_probe(
        cfg, steps_per_epoch=50000 // batch, encoder_variables=enc_vars
    )
    mean, std = stats_for(cfg.dataset)
    aug_cfg = AugmentConfig(size=size, mean=mean, std=std, color_ops=False)
    train_jit, _ = make_probe_steps(
        classifier, tx, encode, aug_cfg, aug_cfg, mesh
    )

    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(batch, size, size, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, size=(batch,)).astype(np.int32)
    sh_images, sh_labels = shard_host_batch((images, labels), mesh)

    # stage token matches the CLI choice (--stage linear) so scripts keying
    # the metric name off the flag find it
    return train_jit, sh_images, sh_labels, state, "linear", (
        f"linear-probe rn50-frozen bf16 rrc+flip lr5 bsz{batch}"
    )


def _setup_ce(mesh, batch, size):
    """The CE-baseline workload: SupCEResNet train step (train/ce.py)."""
    from simclr_pytorch_distributed_tpu.models import SupCEResNet
    from simclr_pytorch_distributed_tpu.ops.augment import AugmentConfig
    from simclr_pytorch_distributed_tpu.ops.schedules import make_lr_schedule
    from simclr_pytorch_distributed_tpu.parallel.mesh import shard_host_batch
    from simclr_pytorch_distributed_tpu.train.ce import CEState, make_ce_steps
    from simclr_pytorch_distributed_tpu.train.linear import stats_for
    from simclr_pytorch_distributed_tpu.train.state import make_optimizer

    data_parallel = mesh.shape["data"]
    model = SupCEResNet(
        model_name="resnet50", num_classes=10, dtype=jnp.bfloat16,
        sync_bn=False, bn_local_groups=data_parallel,
    )
    schedule = make_lr_schedule(
        learning_rate=0.1, epochs=100, steps_per_epoch=50000 // batch,
        cosine=True,
    )
    tx = make_optimizer(schedule, momentum=0.9, weight_decay=1e-4)
    variables = model.init(
        jax.random.key(0), jnp.zeros((2, size, size, 3)), train=True
    )
    state = CEState(
        step=jnp.zeros((), jnp.int32),
        params=variables["params"],
        batch_stats=variables["batch_stats"],
        opt_state=tx.init(variables["params"]),
    )
    mean, std = stats_for("cifar10")
    aug_cfg = AugmentConfig(size=size, mean=mean, std=std, color_ops=False)
    train_jit, _ = make_ce_steps(model, tx, aug_cfg, mesh)

    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(batch, size, size, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, size=(batch,)).astype(np.int32)
    sh_images, sh_labels = shard_host_batch((images, labels), mesh)

    return train_jit, sh_images, sh_labels, state, "ce", (
        f"supervised-CE rn50 bf16 rrc+flip bsz{batch}"
    )


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser("throughput bench")
    ap.add_argument(
        "--stage", choices=["pretrain", "linear", "ce"], default="pretrain",
        help="workload: contrastive pretrain (headline), linear probe, or "
             "the CE baseline — same methodology for all three",
    )
    ap.add_argument(
        "--batch_size", type=int, default=256,
        help="global batch per chip (32 = one v5e-8 shard of the recipe's "
             "256, the per-device workload for the multi-chip projection in "
             "docs/PERF.md)",
    )
    ap.add_argument(
        "--data_placement", choices=["host", "device", "window"],
        default="host",
        help="device = bench the resident-store step (full-epoch HBM buffer "
             "+ in-program slice, the --data_placement device driver "
             "program); window = the windowed-store step (one resident "
             "window, in-program slice at epoch_position %% W) — same "
             "methodology for all arms",
    )
    ap.add_argument(
        "--recipe", choices=["simclr", "supcon", "byol", "simsiam", "vicreg"],
        default="simclr",
        help="SSL recipe arm (recipes/): bench the other loss heads on the "
             "same methodology; vs_baseline stays pinned to the recorded "
             "supcon-family headline so recipe overhead is measured",
    )
    ap.add_argument(
        "--moco_queue", type=int, default=0,
        help="device-side negative queue size for the simclr recipe arm "
             "(multiple of 2*batch_size; forces the dense loss path)",
    )
    ap.add_argument(
        "--ledger", nargs="?", const="docs/perf_ledger.jsonl", default="",
        metavar="PATH",
        help="append this run to the longitudinal perf ledger "
             "(scripts/perf_ledger.py: git rev + workload fingerprint + "
             "throughput per record; default path docs/perf_ledger.jsonl)",
    )
    ap.add_argument(
        "--ledger_phases", default="", metavar="TRACE_REPORT_JSON",
        help="a trace_report artifact whose per-phase shares ride the "
             "ledger record (drift becomes attributable to a phase)",
    )
    ap.add_argument(
        "--ledger_note", default="",
        help="free-form provenance note on the ledger record",
    )
    args = ap.parse_args(argv)
    if args.data_placement != "host" and args.stage != "pretrain":
        ap.error("--data_placement applies to --stage pretrain only")
    if ((args.recipe != "simclr" or args.moco_queue)
            and args.stage != "pretrain"):
        ap.error("--recipe/--moco_queue apply to --stage pretrain only")

    from simclr_pytorch_distributed_tpu.parallel.mesh import create_mesh
    from simclr_pytorch_distributed_tpu.train.supcon import (
        enable_compile_cache,
    )

    n_chips = len(jax.devices())
    device_kind = jax.devices()[0].device_kind
    peak_tflops = peak_for(PEAK_TFLOPS_BY_KIND, device_kind)
    peak_hbm = peak_for(PEAK_HBM_GBPS_BY_KIND, device_kind)
    enable_compile_cache()
    mesh = create_mesh()
    batch, size = args.batch_size, 32

    if args.stage == "pretrain":
        setup = _setup_pretrain(
            mesh, batch, size, data_placement=args.data_placement,
            recipe=args.recipe, moco_queue=args.moco_queue,
        )
    elif args.stage == "linear":
        setup = _setup_linear(mesh, batch, size)
    else:
        setup = _setup_ce(mesh, batch, size)
    jit_fn, sh_images, sh_labels, state, metric_stage, config_str = setup

    fn, flops, bytes_accessed = _compile_with_flops(
        jit_fn, state, sh_images, sh_labels, jax.random.key(0)
    )

    def run_step(state, key):
        return fn(state, sh_images, sh_labels, key)

    # The base key is passed UNCHANGED every step; the per-step key is
    # fold_in(base_key, state.step) INSIDE the jitted program (the drivers
    # do the same). Any per-step host key derivation is an H2D transfer
    # that silently throttled the small probe/CE steps (docs/PERF.md).
    base_key = jax.random.key(42)

    # warmup (compile + first steps); scalar readback = real sync (docstring)
    for i in range(3):
        state, metrics = run_step(state, base_key)
    float(metrics["loss"])

    # Median of credible windows (see module docstring for why not best-of-N).
    n_steps, windows = 30, 5
    window_dts = []
    for w in range(windows):
        t0 = time.perf_counter()
        for i in range(n_steps):
            state, metrics = run_step(state, base_key)
        float(metrics["loss"])  # D2H readback of a computed value: real sync
        window_dts.append(time.perf_counter() - t0)

    def implied_mfu(dt_window: float) -> float:
        # cost_analysis() on an SPMD-partitioned executable reports the
        # PER-DEVICE module's FLOPs, so the per-chip MFU is flops/dt/peak
        # with no n_chips factor (on 1 chip the two conventions coincide).
        return (flops * n_steps / dt_window) / (peak_tflops * 1e12)

    credible = [dt for dt in window_dts if implied_mfu(dt) <= CREDIBLE_MFU]
    n_glitched = len(window_dts) - len(credible)
    if credible:
        dt = statistics.median(credible)
        clock_suspect = False
    else:
        # Every window claims impossible speed: the clock cannot be
        # trusted at all. Report the SLOWEST window (the most
        # conservative sample) and flag it, rather than quoting a number
        # we know is wrong.
        dt = max(window_dts)
        clock_suspect = True

    imgs_per_sec = n_steps * batch / dt
    per_chip = imgs_per_sec / n_chips
    mfu = implied_mfu(dt)
    # Roofline companion to MFU: fraction of peak HBM bandwidth the step's
    # XLA-counted buffer traffic implies. "bytes accessed" is HLO-level
    # (counts each logical buffer touch; fusion means actual DRAM traffic is
    # lower), so this is an UPPER bound on true HBM utilization.
    hbm_util = (bytes_accessed * n_steps / dt) / (peak_hbm * 1e9)
    record = {
        "metric": f"{metric_stage}_imgs_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "imgs/s/chip",
        # baselines were recorded at the recipe defaults on ONE baseline
        # chip (256 imgs/chip); a non-default batch, a multi-chip mesh
        # (global 256 shards to 256/n imgs/chip — a different per-chip
        # workload, see bench_perchip32_r5.json), or any other accelerator
        # is not a regression signal. A non-default --recipe/--moco_queue
        # arm KEEPS vs_baseline: the comparison against the supcon-family
        # headline is the recipe-overhead measurement (the ratchet bench
        # gate only runs the default arm, so the bar never binds on it).
        "vs_baseline": (
            vs_baseline_for(metric_stage, per_chip)
            if args.batch_size == 256 and args.data_placement == "host"
            and n_chips == 1 and device_kind == REPO_BASELINE_DEVICE_KIND
            else 1.0
        ),
        "detail": {
            "global_batch": batch,
            "recipe": getattr(args, "recipe", "simclr"),
            "moco_queue": getattr(args, "moco_queue", 0),
            "chips": n_chips,
            "device_kind": device_kind,
            "total_imgs_per_sec": round(imgs_per_sec, 1),
            "step_ms": round(1000 * dt / n_steps, 2),
            "flops_per_step_per_device": flops,
            "bytes_accessed_per_step_per_device": bytes_accessed,
            "implied_mfu": round(mfu, 4),
            "implied_hbm_util_upper_bound": round(hbm_util, 4),
            "peak_tflops_assumed": peak_tflops,
            "peak_hbm_gbps_assumed": peak_hbm,
            "window_step_ms": [round(1000 * d / n_steps, 2) for d in window_dts],
            "windows_discarded_as_clock_glitch": n_glitched,
            "clock_suspect": clock_suspect,
            "selection": "median of credible windows (implied MFU <= 0.7)",
            "config": config_str,
        },
    }
    print(json.dumps(record))
    if args.ledger:
        # the longitudinal record: one line per bench run, fingerprinted by
        # workload identity so only like compares with like
        import os
        import sys as _sys

        repo = os.path.dirname(os.path.abspath(__file__))
        _sys.path.insert(0, os.path.join(repo, "scripts"))
        import perf_ledger

        # relative paths anchor at the REPO, not the cwd: the committed
        # ledger is what perf_ledger.py check and the ratchet gate read —
        # a cwd-relative default would grow a stray history instead
        ledger_path = args.ledger
        if not os.path.isabs(ledger_path):
            ledger_path = os.path.join(repo, ledger_path)
        ledger_rec = perf_ledger.append_from_bench(
            ledger_path, record, phases_path=args.ledger_phases,
            note=args.ledger_note,
        )
        print(f"ledger: appended {ledger_rec['fingerprint']} "
              f"@ {ledger_rec['git_rev']} -> {ledger_path}")


if __name__ == "__main__":
    main()
