"""Supervised cross-entropy baseline trainer — the trainer main_ce.py LOST.

The reference fork kept only ``set_loader`` of main_ce.py (``main_ce.py:19-68``);
``SupCEResNet`` is imported but never trained (SURVEY.md §2.1 #14). BASELINE.json
still lists the CE-baseline config, so this rebuilds the complete trainer:
SupCEResNet end-to-end with the probe stage's aug stack (RRC+flip, main_ce.py:
31-36), SGD + the shared schedule machinery, top-1/5 validation, best-acc
tracking — distributed over the mesh like the contrastive stage.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax import struct

from simclr_pytorch_distributed_tpu import config as config_lib
from simclr_pytorch_distributed_tpu.data.cifar import (
    ensure_dataset_available,
    load_dataset,
)
from simclr_pytorch_distributed_tpu.data import device_store
from simclr_pytorch_distributed_tpu.data.pipeline import EpochLoader
from simclr_pytorch_distributed_tpu.models import SupCEResNet
from simclr_pytorch_distributed_tpu.ops.augment import (
    AugmentConfig,
    augment_batch,
    eval_batch,
)
from simclr_pytorch_distributed_tpu.ops.losses import cross_entropy_loss
from simclr_pytorch_distributed_tpu.ops.metrics import AverageMeter, topk_correct
from simclr_pytorch_distributed_tpu.ops.schedules import make_lr_schedule
from simclr_pytorch_distributed_tpu.parallel.mesh import (
    batch_sharding,
    broadcast_from_main,
    create_mesh,
    is_main_process,
    replicated_sharding,
    setup_distributed,
    shard_host_batch,
    sync_processes,
)
from simclr_pytorch_distributed_tpu.train.linear import (
    PROBE_METRIC_KEYS,
    jit_scalar_or_ring_step,
    run_validation,
    stats_for,
)
from simclr_pytorch_distributed_tpu.train.supcon import enable_compile_cache
from simclr_pytorch_distributed_tpu.utils import preempt
from simclr_pytorch_distributed_tpu.utils.guard import (
    exit_code_for,
    exit_with_code,
)
from simclr_pytorch_distributed_tpu.utils import tracing
from simclr_pytorch_distributed_tpu.utils.checkpoint import (
    resolve_resume_path,
    restore_checkpoint,
    resume_position,
    save_checkpoint,
    wait_for_saves,
)
from simclr_pytorch_distributed_tpu.utils.logging_utils import TBLogger, setup_logging
from simclr_pytorch_distributed_tpu.utils.obs import RunObservability
from simclr_pytorch_distributed_tpu.utils.profiling import StepTracer
from simclr_pytorch_distributed_tpu.utils.telemetry import TelemetrySession


class CEState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any


def make_ce_steps(
    model, tx, aug_cfg, mesh, metric_ring=None, resident_steps=None,
    window_batches=None,
):
    """``metric_ring`` switches the train step to ring telemetry (see
    train/supcon.make_fused_update); ``None`` keeps the scalar-returning
    signature (bench.py). ``resident_steps`` switches the train step's data
    args to the device-resident epoch buffers, ``window_batches`` narrows
    them to one streaming window (jit_scalar_or_ring_step)."""
    repl = replicated_sharding(mesh)

    def train_step(state: CEState, images_u8, labels, base_key):
        # fold_in INSIDE the program (state.step == the driver's global step;
        # host-side per-step fold_in = an H2D transfer per step, docs/PERF.md)
        key = jax.random.fold_in(base_key, state.step)
        images = augment_batch(key, images_u8, aug_cfg)

        def loss_fn(params):
            logits, mutated = model.apply(
                {"params": params, "batch_stats": state.batch_stats},
                images, train=True, mutable=["batch_stats"],
            )
            return cross_entropy_loss(logits.astype(jnp.float32), labels), (logits, mutated)

        (loss, (logits, mutated)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params
        )
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_state = CEState(
            step=state.step + 1,
            params=optax.apply_updates(state.params, updates),
            batch_stats=mutated["batch_stats"],
            opt_state=new_opt,
        )
        correct = topk_correct(logits, labels)
        return new_state, {"loss": loss, "top1": correct[1], "top5": correct[5]}

    def eval_step(state_vars, images_u8, labels, valid):
        images = eval_batch(images_u8, aug_cfg)
        logits = model.apply(
            {"params": state_vars["params"], "batch_stats": state_vars["batch_stats"]},
            images, train=False,
        ).astype(jnp.float32)
        per_ex = -jax.nn.log_softmax(logits)[jnp.arange(labels.shape[0]), labels]
        hit = jax.lax.top_k(logits, 5)[1] == labels[:, None]
        return {
            "loss_sum": jnp.sum(per_ex * valid),
            "top1": jnp.sum(jnp.any(hit[:, :1], axis=1) * valid),
            "top5": jnp.sum(jnp.any(hit, axis=1) * valid),
            "n": jnp.sum(valid),
        }

    train_jit = jit_scalar_or_ring_step(
        train_step, metric_ring, mesh, resident_steps=resident_steps,
        window_batches=window_batches,
    )
    eval_jit = jax.jit(
        eval_step,
        in_shardings=(repl, batch_sharding(mesh, 4), batch_sharding(mesh, 1),
                      batch_sharding(mesh, 1)),
        out_shardings=repl,
    )
    return train_jit, eval_jit


def run(cfg: config_lib.LinearConfig):
    setup_distributed()
    cfg.save_folder = broadcast_from_main(cfg.save_folder)
    cfg.tb_folder = broadcast_from_main(cfg.tb_folder)
    enable_compile_cache()
    setup_logging(cfg.save_folder, is_main_process())
    mesh = create_mesh()

    ensure_dataset_available(cfg.dataset, cfg.data_folder, cfg.download)
    train_data, test_data, n_cls = load_dataset(
        cfg.dataset, cfg.data_folder,
        allow_synthetic_fallback=(cfg.dataset == "synthetic"),
    )
    cfg.n_cls = n_cls
    loader = EpochLoader(
        train_data["images"], train_data["labels"], cfg.batch_size,
        base_seed=cfg.seed, process_index=jax.process_index(),
        process_count=jax.process_count(),
    )
    steps_per_epoch = len(loader)

    dtype = jnp.bfloat16 if cfg.bf16 else jnp.float32
    # --syncBN off (default) = the reference's per-GPU BatchNorm2d semantics:
    # BN statistics scoped to the data-parallel device slices (models/norm.py
    # grouped mode; conversion is conditional upstream, main_supcon.py:223-224)
    model = SupCEResNet(
        model_name=cfg.model, num_classes=n_cls, dtype=dtype,
        sync_bn=cfg.syncBN,
        bn_local_groups=1 if cfg.syncBN else mesh.shape["data"],
    )
    schedule = make_lr_schedule(
        learning_rate=cfg.learning_rate, epochs=cfg.epochs,
        steps_per_epoch=steps_per_epoch, cosine=cfg.cosine,
        lr_decay_rate=cfg.lr_decay_rate, lr_decay_epochs=cfg.lr_decay_epochs,
        warm=cfg.warm, warm_epochs=cfg.warm_epochs, warmup_from=cfg.warmup_from,
    )
    from simclr_pytorch_distributed_tpu.train.state import make_optimizer

    tx = make_optimizer(schedule, momentum=cfg.momentum, weight_decay=cfg.weight_decay)
    variables = model.init(
        jax.random.key(cfg.seed), jnp.zeros((2, cfg.size, cfg.size, 3)), train=True
    )
    state = CEState(
        step=jnp.zeros((), jnp.int32),
        params=variables["params"],
        batch_stats=variables["batch_stats"],
        opt_state=tx.init(variables["params"]),
    )

    mean, std = stats_for(cfg.dataset)
    aug_cfg = AugmentConfig(size=cfg.size, mean=mean, std=std, color_ops=False)
    # observability stack (docs/OBSERVABILITY.md, utils/obs.py): flight
    # recorder -> <save_folder>/events.jsonl (+ trace.json), stall
    # watchdog on the flush boundary, optional Prometheus sidecar. Built
    # BEFORE the store: placement resolution is the run's first
    # collective, and its span + startup clock anchor (the fleet report's
    # alignment ruler) must land on the record.
    obs = RunObservability(cfg, name="ce")
    # --data_placement (data/device_store.py): HBM-resident train set or a
    # double-buffered streaming window, dispatch-only hot loop either way;
    # 'auto' walks the device->window->host ladder with a banner
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
    # device-side metric ring + background flush (utils/telemetry.py)
    telemetry = TelemetrySession(
        cfg.print_freq, PROBE_METRIC_KEYS, cfg.telemetry,
        watchdog=obs.watchdog, gauges=obs.gauges,
    )
    train_jit, eval_jit = make_ce_steps(
        model, tx, aug_cfg, mesh, metric_ring=telemetry.ring,
        resident_steps=steps_per_epoch if store is not None else None,
        window_batches=None if store is None else store.window_batches,
    )

    start_epoch, start_step = 1, 0
    meta = {}
    if getattr(cfg, "resume", ""):
        # full-state resume, step-granular like the pretrain driver's: the
        # restore goes through the TrainState facade state_for_save already
        # defines for the saver, then maps back onto CEState.
        resume_path = resolve_resume_path(cfg.resume)
        # mesh= -> elastic restore (orbax reshards onto this run's mesh;
        # see the pretrain driver's note and utils/checkpoint.py)
        restored, meta = restore_checkpoint(
            resume_path, state_for_save(state), mesh=mesh
        )
        state = CEState(
            step=restored.step, params=restored.params,
            batch_stats=restored.batch_stats, opt_state=restored.opt_state,
        )
        start_epoch, start_step = resume_position(meta, steps_per_epoch)
        logging.info(
            "resumed from %s at epoch %d step %d",
            resume_path, start_epoch, start_step,
        )

    tb = TBLogger(cfg.tb_folder, enabled=is_main_process())
    base_key = jax.random.key(cfg.seed + 1)
    # windowed jax.profiler capture (utils/profiling.py) — previously
    # reachable only from the supcon driver, so the CE stage could not
    # capture an xplane window
    tracer = StepTracer(
        cfg.trace_dir, cfg.trace_start_step, cfg.trace_steps,
        enabled=is_main_process(),
    )
    # the best-accuracy watermark is RUN state: a resumed run that never
    # re-beats the pre-preemption peak must still report it (checkpoint
    # meta carries it, like the pretrain driver's rollback damping)
    best_acc = float(meta.get("best_acc") or 0.0)
    best_acc5 = float(meta.get("best_acc5") or 0.0)

    def run_meta():
        return {"best_acc": best_acc, "best_acc5": best_acc5}

    def eval_variables(state):
        return {"params": state.params, "batch_stats": state.batch_stats}

    preempt.install()
    # explicit capture for the exit-code gauge (see the pretrain driver's
    # note: sys.exc_info() in a finally also sees enclosing-frame handlers)
    exit_exc = None
    try:
        for epoch in range(start_epoch, cfg.epochs + 1):
            t1 = time.time()
            obs.set_epoch(epoch)
            losses, top1 = AverageMeter(), AverageMeter()
            ring_buf = telemetry.init_buffer(replicated_sharding(mesh))

            def submit_window(boundary_idx, ring_buf, step_hint):
                # one flush_boundary (utils/telemetry.py): snapshot + queue
                # the one-transfer flush (meters/log run on the telemetry
                # thread, FIFO), observe failures collectively
                def consume(fetched):
                    for _, m in fetched:
                        losses.update(m["loss"], cfg.batch_size)
                        top1.update(100.0 * m["top1"] / cfg.batch_size, cfg.batch_size)
                    logging.info(
                        "Train: [%d][%d/%d]\tloss %.3f (%.3f)\tAcc@1 %.3f (%.3f)",
                        epoch, boundary_idx + 1, steps_per_epoch,
                        losses.val, losses.avg, top1.val, top1.avg,
                    )

                telemetry.flush_boundary(ring_buf, consume,
                                         step_hint=step_hint)

            ss = start_step if epoch == start_epoch else 0
            # both loop shapes iterate range(ss, steps_per_epoch) — an
            # oversized resume offset (changed geometry) must raise, not
            # silently complete a zero-step epoch
            loader.check_start_step(ss)
            batches = None if store is not None else loader.epoch(
                epoch, start_step=ss
            )
            try:
                epoch_span = tracing.span("epoch", track="main:epoch",
                                          epoch=epoch)
                epoch_span.__enter__()
                for idx in range(ss, steps_per_epoch):
                    gstep = (epoch - 1) * steps_per_epoch + idx  # == state.step
                    # first dispatch of the run carries trace+compile
                    # (main:compile phase; see train/supcon.py)
                    span = (
                        tracing.span("first_step", track="main:compile",
                                     step=gstep)
                        if epoch == start_epoch and idx == ss
                        else contextlib.nullcontext()
                    )
                    if batches is None:
                        epoch_images, epoch_labels = store.batch_buffers(
                            epoch, idx
                        )
                        with span:
                            state, ring_buf = train_jit(
                                state, ring_buf, epoch_images, epoch_labels,
                                base_key
                            )
                    else:
                        images_u8, labels = next(batches)
                        batch = shard_host_batch((images_u8, labels), mesh)
                        with span:
                            state, ring_buf = train_jit(
                                state, ring_buf, batch[0], batch[1], base_key
                            )
                    telemetry.append(idx, gstep)
                    if tracer is not None:
                        tracer.step(gstep)
                    if (idx + 1) % cfg.print_freq == 0 or idx + 1 == steps_per_epoch:
                        submit_window(idx, ring_buf, gstep)
                        if idx + 1 < steps_per_epoch and preempt.requested_global():
                            # SIGTERM/SIGINT at a flush boundary, decided
                            # collectively on the MAIN thread (see
                            # train/supcon.py — independent of any in-flight
                            # flush). Drain COLLECTIVELY (a host-local raise
                            # here would skip the collective emergency save
                            # while peers enter it) so the mid-epoch save —
                            # collective, same semantics as the pretrain driver
                            # — sees complete metrics; the distinct exit code
                            # tells the launcher to re-run with --resume.
                            telemetry.drain_global(gstep)
                            tracing.event(
                                "preempt_exit", track="main:guard",
                                epoch=epoch, step_in_epoch=idx + 1,
                            )
                            preempt.emergency_save_and_exit(
                                cfg.save_folder,
                                f"preempt_epoch_{epoch}_step_{idx + 1}",
                                state_for_save(state),
                                config_lib.config_dict(cfg), epoch - 1,
                                step_in_epoch=idx + 1, extra_meta=run_meta(),
                                cleanup=(tb.close, telemetry.close),
                            )
            finally:
                epoch_span.__exit__(None, None, None)
                if batches is not None:
                    batches.close()  # stop the prefetch worker on early exit
            # flush any short-epoch tail, then drain COLLECTIVELY ahead of
            # the scheduled save (the ordering contract lives on the session)
            telemetry.finish_epoch(
                lambda hint: submit_window(steps_per_epoch - 1, ring_buf, hint),
                epoch * steps_per_epoch - 1,
            )
            logging.info("Train epoch %d, total time %.2f, accuracy:%.2f",
                         epoch, time.time() - t1, top1.avg)

            with tracing.span("validation", track="main:eval", epoch=epoch):
                val = run_validation(
                    eval_jit, eval_variables(state), test_data["images"],
                    test_data["labels"], cfg.val_batch_size, mesh,
                )
            logging.info(" * Acc@1 %.3f, Acc@5 %.3f", val["top1"], val["top5"])
            if is_main_process():
                tb.log_value("ce/train_loss", losses.avg, epoch)
                tb.log_value("ce/train_acc1", top1.avg, epoch)
                tb.log_value("ce/val_loss", val["loss"], epoch)
                tb.log_value("ce/val_acc1", val["top1"], epoch)
                tb.log_value("ce/val_acc5", val["top5"], epoch)
            if val["top1"] > best_acc:
                best_acc, best_acc5 = val["top1"], val["top5"]
            if epoch % cfg.save_freq == 0:
                # collective on all processes (orbax coordinates writers;
                # meta.json stays process-0-gated inside save_checkpoint)
                save_checkpoint(
                    cfg.save_folder, f"ckpt_epoch_{epoch}",
                    # CEState quacks enough like TrainState for the saver
                    state_for_save(state), config=config_lib.config_dict(cfg),
                    epoch=epoch, block=False, extra_meta=run_meta(),
                )
            if preempt.requested_global():
                # boundary preemption (collective decision): this epoch is
                # persisted (by the scheduled save above, or a preempt_*
                # save now), then the distinct exit
                tracing.event(
                    "preempt_exit", track="main:guard", epoch=epoch,
                )
                preempt.emergency_save_and_exit(
                    cfg.save_folder,
                    None if epoch % cfg.save_freq == 0
                    else f"preempt_epoch_{epoch}",
                    state_for_save(state), config_lib.config_dict(cfg),
                    epoch, extra_meta=run_meta(),
                    cleanup=(tb.close, telemetry.close),
                )

    except BaseException as e:
        exit_exc = e
        raise
    finally:
        preempt.uninstall()
        telemetry.close()
        if store is not None:
            store.close()  # stop the window prefetch worker on any exit
        tracer.close()
        # drain in-flight async saves BEFORE the observability teardown
        # (utils/obs.py ordering contract: the final checkpoint_commit span
        # must land in the record, and the watchdog must still be watching
        # if that drain wedges); the post-loop wait below is then a no-op
        wait_for_saves()
        obs.close(exit_code=exit_code_for(exit_exc))
    wait_for_saves()
    logging.info("best accuracy: %.2f, accuracy5: %.2f", best_acc, best_acc5)
    tb.close()
    sync_processes("ce_run_end")
    return best_acc, best_acc5


def state_for_save(state: CEState):
    from simclr_pytorch_distributed_tpu.train.state import TrainState

    # The placeholder scalar must inherit the step's mesh-replicated global
    # sharding: a fresh jnp.zeros(()) is a host-local single-device array and
    # orbax REFUSES to serialize those in a multi-process job (found by
    # tests/test_multiprocess.py::test_two_process_ce_driver).
    return TrainState(
        step=state.step, params=state.params, batch_stats=state.batch_stats,
        opt_state=state.opt_state,
        record_norm_mean=(state.step * 0).astype(jnp.float32),
    )


def main(argv=None):
    cfg = config_lib.parse_linear(argv, ce=True)
    # typed exit codes (docs/RESILIENCE.md): NaN/flush aborts exit 1/2,
    # preemption 75 via SystemExit — the supervisor's classification input
    exit_with_code(lambda: run(cfg))


if __name__ == "__main__":
    main()
