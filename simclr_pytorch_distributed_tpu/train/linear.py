"""Linear-probe evaluation driver — main_linear.py, TPU-native.

Semantics from the reference (SURVEY.md §3.4):

- the pretrained encoder is FROZEN and in eval mode: BN uses running statistics
  and nothing updates (``model.eval()`` + ``torch.no_grad()`` + ``.detach()``,
  ``main_linear.py:149,170-172``) — here the encoder runs ``train=False`` under
  ``stop_gradient`` and only classifier params are in the optimizer;
- train aug is RRC(0.2-1)+flip only, val is normalize only
  (``main_ce.py:31-41`` via ``main_linear.py:12,253``);
- SGD on the classifier with step decay 60/75/90 x0.2 by default, 100 epochs;
  top-1/top-5 tracked, best val acc reported at the end
  (``main_linear.py:284-288``) — the number the README tables quote.

The probe runs data-parallel over the mesh (the reference is single-GPU; here
extra chips just shard the batch — the math is identical because the encoder is
frozen and CE is a per-example mean).
"""

from __future__ import annotations


import contextlib
import logging
import time
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from simclr_pytorch_distributed_tpu import config as config_lib
from simclr_pytorch_distributed_tpu.data.cifar import (
    ensure_dataset_available,
    load_dataset,
)
from simclr_pytorch_distributed_tpu.data import device_store
from simclr_pytorch_distributed_tpu.data.device_store import slice_epoch_step
from simclr_pytorch_distributed_tpu.data.pipeline import EpochLoader
from simclr_pytorch_distributed_tpu.models import (
    LinearClassifier,
    SupConResNet,
)
from simclr_pytorch_distributed_tpu.ops.augment import (
    DATASET_STATS,
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
    epoch_buffer_sharding,
    is_main_process,
    replicated_sharding,
    setup_distributed,
    shard_host_batch,
    sync_processes,
)
from simclr_pytorch_distributed_tpu.train.state import make_optimizer
from simclr_pytorch_distributed_tpu.train.supcon import enable_compile_cache
from simclr_pytorch_distributed_tpu.train.supcon_step import epoch_position
from simclr_pytorch_distributed_tpu.utils import preempt
from simclr_pytorch_distributed_tpu.utils.guard import (
    exit_code_for,
    exit_with_code,
)
from simclr_pytorch_distributed_tpu.utils.checkpoint import (
    load_pretrained_variables,
    save_classifier,
)
from simclr_pytorch_distributed_tpu.utils.logging_utils import TBLogger, setup_logging
from simclr_pytorch_distributed_tpu.utils import tracing
from simclr_pytorch_distributed_tpu.utils.obs import RunObservability
from simclr_pytorch_distributed_tpu.utils.profiling import StepTracer
from simclr_pytorch_distributed_tpu.utils.telemetry import TelemetrySession

# ring columns for the probe/CE step metrics (ops/metrics.MetricRing)
PROBE_METRIC_KEYS = ("loss", "top1", "top5")


class ProbeState(struct.PyTreeNode):
    step: jax.Array
    params: Any  # classifier params only
    opt_state: Any


def stats_for(dataset: str) -> Tuple[Tuple[float, ...], Tuple[float, ...]]:
    if dataset in DATASET_STATS:
        return DATASET_STATS[dataset]
    return ((0.5, 0.5, 0.5), (0.25, 0.25, 0.25))  # synthetic


def build_probe(cfg: config_lib.LinearConfig, steps_per_epoch: int, encoder_variables):
    dtype = jnp.bfloat16 if cfg.bf16 else jnp.float32
    encoder = SupConResNet(model_name=cfg.model, dtype=dtype)
    classifier = LinearClassifier(model_name=cfg.model, num_classes=cfg.n_cls)
    schedule = make_lr_schedule(
        learning_rate=cfg.learning_rate, epochs=cfg.epochs,
        steps_per_epoch=steps_per_epoch, cosine=cfg.cosine,
        lr_decay_rate=cfg.lr_decay_rate, lr_decay_epochs=cfg.lr_decay_epochs,
        warm=cfg.warm, warm_epochs=cfg.warm_epochs, warmup_from=cfg.warmup_from,
    )
    tx = make_optimizer(schedule, momentum=cfg.momentum, weight_decay=cfg.weight_decay)
    feat_dim = encoder.encoder_dim
    cls_params = classifier.init(
        jax.random.key(cfg.seed), jnp.zeros((2, feat_dim))
    )["params"]
    state = ProbeState(
        step=jnp.zeros((), jnp.int32), params=cls_params, opt_state=tx.init(cls_params)
    )

    def encode(images):
        feats = encoder.apply(
            {"params": encoder_variables["params"],
             "batch_stats": encoder_variables["batch_stats"]},
            images, train=False, method=SupConResNet.encode,
        )
        return jax.lax.stop_gradient(feats.astype(jnp.float32))

    return encoder, classifier, schedule, tx, state, encode


def jit_scalar_or_ring_step(
    step_fn, metric_ring, mesh, resident_steps=None, window_batches=None
):
    """Jit a ``(state, images_u8, labels, key) -> (state, metrics)`` train
    step for a probe-style driver. With ``metric_ring`` the step is wrapped
    to write its metrics into the donated device ring at ``state.step``
    (``(state, ring, images, labels, key) -> (state, ring)``, see
    train/supcon.make_fused_update); ``None`` keeps the scalar-returning
    signature (bench.py). ``resident_steps`` (the loader's steps_per_epoch)
    switches the data arguments to the device-resident ``[steps, batch, ...]``
    epoch buffers (data/device_store.py): the program slices its own batch
    at ``state.step % resident_steps`` and the buffers are NOT donated;
    ``window_batches`` additionally narrows them to one streaming window
    (a WindowStore) by reducing the position modulo the window length (see
    train/supcon.make_fused_update). Shared by the probe and CE builders so
    the ring/resident wiring (shardings + donation) cannot diverge between
    them."""
    repl = replicated_sharding(mesh)
    if resident_steps is None:
        data = (batch_sharding(mesh, 4), batch_sharding(mesh, 1))
        sliced_step = step_fn
    else:
        data = (epoch_buffer_sharding(mesh, 5), epoch_buffer_sharding(mesh, 2))

        def sliced_step(state, epoch_images, epoch_labels, base_key):
            pos = epoch_position(state.step, resident_steps)
            if window_batches is not None:
                pos = pos % window_batches
            images_u8, labels = slice_epoch_step(
                epoch_images, epoch_labels, pos
            )
            return step_fn(state, images_u8, labels, base_key)

    if metric_ring is None:
        return jax.jit(
            sliced_step,
            in_shardings=(repl, *data, repl),
            out_shardings=(repl, repl),
            donate_argnums=(0,),
        )

    def ring_step(state, ring, images_arg, labels_arg, base_key):
        new_state, metrics = sliced_step(state, images_arg, labels_arg, base_key)
        return new_state, metric_ring.write(ring, metrics, state.step)

    return jax.jit(
        ring_step,
        in_shardings=(repl, repl, *data, repl),
        out_shardings=(repl, repl),
        donate_argnums=(0, 1),
    )


def make_probe_steps(
    classifier, tx, encode, aug_cfg, eval_cfg, mesh, metric_ring=None,
    resident_steps=None, window_batches=None,
):
    """``metric_ring`` switches the train step to ring telemetry —
    ``(state, ring, images, labels, key) -> (state, ring)`` with the metrics
    written on device (see train/supcon.make_fused_update); ``None`` keeps
    the scalar-returning signature (bench.py). ``resident_steps`` switches
    the train step's data args to the device-resident epoch buffers
    (jit_scalar_or_ring_step); validation always streams from the host (it
    runs once per epoch — not a hot path)."""
    repl = replicated_sharding(mesh)

    def train_step(state: ProbeState, images_u8, labels, base_key):
        # fold_in INSIDE the program (state.step == the driver's global
        # step): a host-side per-step fold_in costs an H2D scalar transfer
        # that throttled this small step in round 5 (docs/PERF.md)
        key = jax.random.fold_in(base_key, state.step)
        images = augment_batch(key, images_u8, aug_cfg)

        def loss_fn(params):
            logits = classifier.apply({"params": params}, encode(images))
            return cross_entropy_loss(logits, labels), logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_state = ProbeState(
            step=state.step + 1,
            params=optax.apply_updates(state.params, updates),
            opt_state=new_opt,
        )
        correct = topk_correct(logits, labels)
        metrics = {"loss": loss, "top1": correct[1], "top5": correct[5]}
        return new_state, metrics

    def eval_step(params, images_u8, labels, valid):
        images = eval_batch(images_u8, eval_cfg)
        logits = classifier.apply({"params": params}, encode(images))
        per_ex = -jax.nn.log_softmax(logits)[jnp.arange(labels.shape[0]), labels]
        loss_sum = jnp.sum(per_ex * valid)
        maxk_hit = jax.lax.top_k(logits, 5)[1] == labels[:, None]
        top1 = jnp.sum(jnp.any(maxk_hit[:, :1], axis=1) * valid)
        top5 = jnp.sum(jnp.any(maxk_hit, axis=1) * valid)
        return {"loss_sum": loss_sum, "top1": top1, "top5": top5, "n": jnp.sum(valid)}

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


def run_validation(eval_jit, params, val_images, val_labels, batch_size, mesh):
    """Full-val top-1/top-5 (reference validate(), main_linear.py:204-244).

    The tail batch is padded to a static shape and masked so the jit never
    recompiles; every example counts exactly once.
    """
    n = len(val_images)
    totals = None
    for lo in range(0, n, batch_size):
        chunk_img = val_images[lo:lo + batch_size]
        chunk_lab = val_labels[lo:lo + batch_size]
        valid = np.ones(len(chunk_img), np.float32)
        pad = batch_size - len(chunk_img)
        if pad:
            chunk_img = np.concatenate([chunk_img, np.repeat(chunk_img[:1], pad, 0)])
            chunk_lab = np.concatenate([chunk_lab, np.repeat(chunk_lab[:1], pad)])
            valid = np.concatenate([valid, np.zeros(pad, np.float32)])
        batch = shard_host_batch((chunk_img, chunk_lab, valid), mesh)
        m = eval_jit(params, *batch)
        # accumulate ON DEVICE: a float() here would sync every batch and
        # stall the async dispatch pipeline (round-3 weak #5); the single
        # readback below is the only host sync of the validation pass
        totals = m if totals is None else jax.tree.map(jnp.add, totals, m)
    totals = {k: float(v) for k, v in totals.items()}
    return {
        "loss": totals["loss_sum"] / totals["n"],
        "top1": 100.0 * totals["top1"] / totals["n"],
        "top5": 100.0 * totals["top5"] / totals["n"],
    }


def run(cfg: config_lib.LinearConfig):
    setup_distributed()
    # the collective classifier save needs every process writing into
    # process 0's timestamped run folder (ce.py/supcon.py do the same)
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
    # observability stack (docs/OBSERVABILITY.md, utils/obs.py): flight
    # recorder -> <save_folder>/events.jsonl (+ trace.json), stall
    # watchdog on the flush boundary, optional Prometheus sidecar. Built
    # BEFORE the store: placement resolution is the run's first
    # collective, and its span + startup clock anchor (the fleet report's
    # alignment ruler) must land on the record.
    obs = RunObservability(cfg, name="linear")
    # --data_placement (data/device_store.py): 'device' keeps the train set
    # HBM-resident, 'window' streams a double-buffered window — the probe
    # step is SMALL, so the per-step H2D was a proportionally bigger slice
    # of its loop than the pretrain driver's
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

    # encoder variables from the pretrain checkpoint (main_linear.py:125-142)
    dtype = jnp.bfloat16 if cfg.bf16 else jnp.float32
    enc_model = SupConResNet(model_name=cfg.model, dtype=dtype)
    abstract = enc_model.init(
        jax.random.key(0), jnp.zeros((2, cfg.size, cfg.size, 3)), train=False
    )
    if cfg.ckpt:
        encoder_variables = load_pretrained_variables(
            cfg.ckpt, {"params": abstract["params"], "batch_stats": abstract["batch_stats"]}
        )
        logging.info("loaded encoder from %s", cfg.ckpt)
    else:
        logging.warning("--ckpt not given: probing a RANDOM encoder")
        encoder_variables = {
            "params": abstract["params"], "batch_stats": abstract["batch_stats"]
        }

    _, classifier, schedule, tx, state, encode = build_probe(
        cfg, steps_per_epoch, encoder_variables
    )
    mean, std = stats_for(cfg.dataset)
    aug_cfg = AugmentConfig(size=cfg.size, mean=mean, std=std, color_ops=False)
    # device-side metric ring + background flush (utils/telemetry.py): the
    # probe step is SMALL, so the per-window sync flush was a proportionally
    # bigger slice of its loop than the pretrain driver's
    telemetry = TelemetrySession(
        cfg.print_freq, PROBE_METRIC_KEYS, cfg.telemetry,
        watchdog=obs.watchdog, gauges=obs.gauges,
    )
    train_jit, eval_jit = make_probe_steps(
        classifier, tx, encode, aug_cfg, aug_cfg, mesh,
        metric_ring=telemetry.ring,
        resident_steps=steps_per_epoch if store is not None else None,
        window_batches=None if store is None else store.window_batches,
    )

    tb = TBLogger(cfg.tb_folder, enabled=is_main_process())
    base_key = jax.random.key(cfg.seed + 1)
    # windowed jax.profiler capture (utils/profiling.py) — previously
    # reachable only from the supcon driver, so the probe stage could not
    # capture an xplane window
    tracer = StepTracer(
        cfg.trace_dir, cfg.trace_start_step, cfg.trace_steps,
        enabled=is_main_process(),
    )
    best_acc, best_acc5 = 0.0, 0.0
    best_params = None

    # The probe has no full-state checkpoints to resume (epochs are seconds,
    # not hours), but it still honors the fleet's SIGTERM contract: finish
    # the flush window, persist the best classifier so far, exit with the
    # preemption code so the launcher knows no re-run bookkeeping is lost.
    # The launcher's blanket "re-run with --resume" relaunch is accepted
    # (config.linear_parser) and means: retrain from scratch.
    if getattr(cfg, "resume", ""):
        logging.warning(
            "--resume %s: the probe keeps no full-state checkpoints; "
            "retraining from scratch", cfg.resume,
        )
    preempt.install()
    preempted = False
    # explicit capture for the exit-code gauge (see the pretrain driver's
    # note: sys.exc_info() in a finally also sees enclosing-frame handlers)
    exit_exc = None
    try:
        for epoch in range(1, cfg.epochs + 1):
            t1 = time.time()
            obs.set_epoch(epoch)
            losses, top1, top5 = AverageMeter(), AverageMeter(), AverageMeter()
            bt = AverageMeter()
            bsz = cfg.batch_size
            ring_buf = telemetry.init_buffer(replicated_sharding(mesh))
            telemetry.start_window_clock()

            def submit_window(boundary_idx, ring_buf, step_hint):
                # one flush_boundary (utils/telemetry.py): meter the window
                # on the main thread, snapshot + queue the one-transfer
                # flush, observe failures collectively
                def consume(fetched, bt):
                    # ``bt`` shadows the meter with the (val, avg) tuple
                    # flush_boundary snapshotted on the main thread — the
                    # live meter keeps mutating while this job runs
                    for _, m in fetched:
                        losses.update(m["loss"], bsz)
                        top1.update(100.0 * m["top1"] / bsz, bsz)
                        top5.update(100.0 * m["top5"] / bsz, bsz)
                    logging.info(
                        "Train: [%d][%d/%d]\tBT %.3f (%.3f)\tloss %.3f (%.3f)\t"
                        "Acc@1 %.3f (%.3f)",
                        epoch, boundary_idx + 1, steps_per_epoch, bt[0], bt[1],
                        losses.val, losses.avg, top1.val, top1.avg,
                    )

                telemetry.flush_boundary(ring_buf, consume, batch_meter=bt,
                                         step_hint=step_hint)

            batches = None if store is not None else loader.epoch(epoch)
            try:
                with tracing.span("epoch", track="main:epoch", epoch=epoch):
                    for idx in range(steps_per_epoch):
                        gstep = (epoch - 1) * steps_per_epoch + idx  # == state.step
                        # first dispatch of the run carries trace+compile
                        # (main:compile phase; see train/supcon.py) — every
                        # later step takes the nullcontext arm
                        span = (
                            tracing.span("first_step", track="main:compile",
                                         step=gstep)
                            if epoch == 1 and idx == 0
                            else contextlib.nullcontext()
                        )
                        if batches is None:
                            epoch_images, epoch_labels = store.batch_buffers(
                                epoch, idx
                            )
                            with span:
                                state, ring_buf = train_jit(
                                    state, ring_buf, epoch_images,
                                    epoch_labels, base_key
                                )
                        else:
                            images_u8, labels = next(batches)
                            batch = shard_host_batch((images_u8, labels), mesh)
                            with span:
                                state, ring_buf = train_jit(
                                    state, ring_buf, batch[0], batch[1],
                                    base_key
                                )
                        telemetry.append(idx, gstep)
                        if tracer is not None:
                            tracer.step(gstep)
                        if (idx + 1) % cfg.print_freq == 0 or idx + 1 == steps_per_epoch:
                            submit_window(idx, ring_buf, gstep)
                            if preempt.requested_global():
                                # collective decision (see train/supcon.py),
                                # on the MAIN thread — independent of any
                                # in-flight flush: all hosts leave the loop
                                # at the same boundary, keeping the
                                # end-of-run barriers matched
                                preempted = True
                                break
            finally:
                if batches is not None:
                    batches.close()  # stop the prefetch worker on early exit
            # flush any short-epoch tail, then drain COLLECTIVELY ahead of
            # the end-of-run save (the ordering contract lives on the session)
            telemetry.finish_epoch(
                lambda hint: submit_window(steps_per_epoch - 1, ring_buf, hint),
                epoch * steps_per_epoch - 1,
            )
            if preempted:
                tracing.event("preempt_exit", track="main:guard", epoch=epoch)
                logging.warning(
                    "preempted (%s) during epoch %d: stopping the probe",
                    preempt.signal_name(), epoch,
                )
                break
            logging.info(
                "Train epoch %d, total time %.2f, accuracy:%.2f",
                epoch, time.time() - t1, top1.avg,
            )
            if is_main_process():
                tb.log_value("classifier/train_loss", losses.avg, epoch)
                tb.log_value("classifier/train_acc1", top1.avg, epoch)
                tb.log_value("classifier/train_acc5", top5.avg, epoch)

            with tracing.span("validation", track="main:eval", epoch=epoch):
                val = run_validation(
                    eval_jit, state.params, test_data["images"],
                    test_data["labels"], cfg.val_batch_size, mesh,
                )
            logging.info(" * Acc@1 %.3f, Acc@5 %.3f", val["top1"], val["top5"])
            if is_main_process():
                tb.log_value("classifier/val_loss", val["loss"], epoch)
                tb.log_value("classifier/val_acc1", val["top1"], epoch)
                tb.log_value("classifier/val_acc5", val["top5"], epoch)
            if val["top1"] > best_acc:
                best_acc, best_acc5 = val["top1"], val["top5"]
                best_params = jax.device_get(state.params)
    except BaseException as e:
        exit_exc = e
        raise
    finally:
        preempt.uninstall()
        telemetry.close()
        if store is not None:
            store.close()  # stop the window prefetch worker on any exit
        tracer.close()
        # no async saves in the probe (save_classifier is blocking), so
        # the observability teardown has nothing to wait for. The probe's
        # preemption exit (SystemExit(75)) is raised AFTER this finally —
        # unlike the pretrain driver's in-try raise — so the terminal
        # exit-code gauge reads the `preempted` flag, not exc_info.
        obs.close(exit_code=(
            preempt.EXIT_PREEMPTED if preempted
            else exit_code_for(exit_exc)
        ))

    if best_params is not None:
        # beyond parity: persist the best probe head (the reference only
        # reports best_acc, main_linear.py:284-288); collective orbax save
        path = save_classifier(cfg.save_folder, best_params, best_acc)
        logging.info("saved best classifier to %s", path)
    logging.info("best accuracy: %.2f, accuracy5: %.2f", best_acc, best_acc5)
    tb.close()
    if preempted:
        sync_processes("linear_run_preempted")
        raise SystemExit(preempt.EXIT_PREEMPTED)
    sync_processes("linear_run_end")
    return best_acc, best_acc5


def main(argv=None):
    cfg = config_lib.parse_linear(argv)
    # typed exit codes (docs/RESILIENCE.md): NaN/flush aborts exit 1/2,
    # preemption 75 via SystemExit — the supervisor's classification input
    exit_with_code(lambda: run(cfg))


if __name__ == "__main__":
    main()
