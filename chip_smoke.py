#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process drives the main path through the functions behind the CLIs, at
the launcher's own geometry (``run_supcon.sh``: ResNet-50, batch 256, 32x32,
fp32, SyncBN), on synthetic data made from the seed:

    python chip_smoke.py            # one chip: pretrain -> probe -> /embed
    python chip_smoke.py --chips 4  # the mesh: pretrain on 4 + a 4-vs-1 step

It needs a TPU: without one it exits non-zero before it imports a trainer.
Any phase that raises ends the script at once. The last line of stdout is the
result, ``{"ok": true, "device": {...}}``; the lines before it are free-form
facts about the run (versions, cache dir, per-phase wall seconds and peak
device memory, the compile seconds that the pretrain's and the probe's own
flight recorders counted, the driver's own BT).

``--rehearse`` shrinks the sizes so the control flow can be walked on the CPU
(add ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for ``--chips
4``); a rehearsal never prints an ``"ok"`` key.
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import dataclasses
import glob
import importlib.metadata
import json
import logging
import math
import os
import re
import tempfile
import time
import urllib.request

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

SYNTHETIC_TRAIN_IMAGES = 1792  # data/cifar.py's synthetic train split

# run_supcon.sh's flags as they stand (model/size/dtype are its defaults)
LAUNCHER_FLAGS = [
    "--syncBN", "--batch_size", "256", "--learning_rate", "0.5",
    "--temp", "0.5", "--cosine", "--method", "SimCLR", "--ngpu", "2",
]
REHEARSAL_FLAGS = [
    "--syncBN", "--batch_size", "64", "--learning_rate", "0.5",
    "--temp", "0.5", "--cosine", "--method", "SimCLR", "--ngpu", "2",
    "--model", "resnet18", "--size", "8",
]


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {what}")


@contextlib.contextmanager
def phase(name: str):
    """Wall seconds of one phase and the devices' peak memory after it."""
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    peaks = [d.memory_stats()["peak_bytes_in_use"] if d.platform == "tpu"
             else 0 for d in jax.devices()]
    say(f"phase {name}: wall {wall:.2f}s, peak_bytes_in_use per device {peaks}")


def compile_record(name: str, run: str) -> None:
    """What a driver's own flight recorder saw compile (track ``compile``,
    utils/tracing.forward_compile_events): seconds inside XLA's backend
    compile *or* its persistent-cache read, summed over programs, and how
    many of them the cache answered (each span's ``cache_hit``)."""
    from simclr_pytorch_distributed_tpu.utils import tracing

    compiles = [e["args"] for e in tracing.load_events_jsonl(os.path.join(run, "events.jsonl"))
                if e.get("track") == tracing.COMPILE_TRACK and e["name"] == "backend_compile"]
    check(compiles, f"{name}: <run>/events.jsonl records no backend_compile")
    slowest = max(compiles, key=lambda a: a["duration_s"])
    say(f"phase {name}: compile {sum(a['duration_s'] for a in compiles):.2f}s "
        f"over {len(compiles)} programs "
        f"({sum(bool(a.get('cache_hit')) for a in compiles)} cache hits), "
        f"slowest {slowest.get('fun_name', '?')} {slowest['duration_s']:.2f}s")


def _drop_file_log_handlers() -> None:
    """Each driver hangs its run's ``log-ing`` on the root logger; between
    phases of one process the previous run's file must stop receiving."""
    root = logging.getLogger()
    for h in list(root.handlers):
        if isinstance(h, logging.FileHandler):
            root.removeHandler(h)
            h.close()


def versions() -> None:
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}")
    say(f"devices: {jax.devices()}")


def sync_retest() -> None:
    """bench.py's docstring says ``block_until_ready`` acked early on the
    round-1 machine. Time one jitted chain of large matmuls both ways on this
    one; print both, assert nothing."""

    @jax.jit
    def chain(x):
        y = jax.lax.fori_loop(0, 64, lambda _, a: (a @ x) * (1.0 / 4096), x)
        return y, jnp.sum(y.astype(jnp.float32))

    x = jnp.ones((4096, 4096), jnp.bfloat16)
    float(chain(x)[1])  # compile + warm
    block_ms, scalar_ms = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        chain(x)[0].block_until_ready()
        block_ms.append(round((time.perf_counter() - t0) * 1e3, 3))
        t0 = time.perf_counter()
        float(chain(x)[1])
        scalar_ms.append(round((time.perf_counter() - t0) * 1e3, 3))
    say(f"sync re-test, 64 chained 4096^3 bf16 matmuls (8.8 TFLOP), ms per "
        f"call: ending in block_until_ready {block_ms}, ending in a scalar "
        f"readback {scalar_ms}")


def pretrain(out: str, flags: list, n_devices: int) -> str:
    """``main_supcon`` for one epoch; returns the run dir."""
    from simclr_pytorch_distributed_tpu.train import supcon

    batch = int(flags[flags.index("--batch_size") + 1])
    steps = SYNTHETIC_TRAIN_IMAGES // batch
    on_tpu = jax.default_backend() == "tpu"
    with phase("pretrain"):
        supcon.main(flags + [
            "--dataset", "synthetic", "--epochs", "1", "--print_freq", "1",
            "--save_freq", "1", "--workdir", out,
        ])
    _drop_file_log_handlers()
    logs = glob.glob(os.path.join(out, "synthetic_models", "*", "log-ing"))
    check(len(logs) == 1, f"expected one pretrain log-ing, found {logs}")
    run = os.path.dirname(logs[0])
    with open(os.path.join(run, "log-ing")) as f:
        log = f.read()
    rows = re.findall(
        r"Train: \[1\]\[(\d+)/(\d+)\]\tBT (\S+) \(\S+\).*?\tloss (\S+) ", log
    )
    check(
        [int(i) for i, _, _, _ in rows] == list(range(1, steps + 1))
        and all(int(n) == steps for _, n, _, _ in rows),
        f"expected {steps} Train lines in log-ing, got {rows}",
    )
    losses = [float(loss) for _, _, _, loss in rows]
    check(all(math.isfinite(v) for v in losses), f"non-finite loss: {losses}")
    say(f"pretrain losses {losses}")
    say(f"pretrain driver BT per step (s, first includes compile) "
        f"{[float(bt) for _, _, bt, _ in rows]}")

    banners = [ln for ln in log.splitlines() if "[loss_impl]" in ln]
    check(len(banners) == 1, f"expected one [loss_impl] banner, got {banners}")
    loss_banner = banners[0]
    say(loss_banner.split(" INFO ", 1)[-1])
    # on the chip 'auto' must pick the fused loss kernel; the CPU rehearsal
    # has no Mosaic and resolves the loss to dense
    check(f"resolved '{'fused' if on_tpu else 'dense'}'" in loss_banner,
          "loss_impl banner names the wrong implementation")
    if n_devices > 1 and on_tpu:
        check(f"data={n_devices}" in loss_banner,
              f"loss_impl banner does not name data={n_devices}")
    check("data_placement: device" in log, "data_placement is not device")
    check(os.path.isdir(os.path.join(run, "last")), "<run>/last missing")
    check(os.path.isfile(os.path.join(run, "events.jsonl")),
          "<run>/events.jsonl missing")
    compile_record("pretrain", run)
    return run


def probe(out: str, run: str, model_flags: list) -> None:
    """``main_linear`` (run_linear.sh's flags) on ``<run>/last`` for one
    epoch; the image size comes from the checkpoint's config."""
    from simclr_pytorch_distributed_tpu.train import linear

    with phase("probe"):
        linear.main(model_flags + [
            "--dataset", "synthetic", "--ckpt", os.path.join(run, "last"),
            "--epochs", "1", "--learning_rate", "5", "--batch_size", "256",
            "--print_freq", "1", "--workdir", out,
        ])
    _drop_file_log_handlers()
    logs = [p for p in glob.glob(os.path.join(out, "**", "log-ing"),
                                 recursive=True)
            if os.path.dirname(p) != run]
    check(len(logs) == 1, f"expected one probe log-ing, found {logs}")
    compile_record("probe", os.path.dirname(logs[0]))
    with open(logs[0]) as f:
        log = f.read()
    losses = [float(v) for v in
              re.findall(r"Train: \[1\]\[\d+/\d+\]\tBT \S+ \(\S+\)\tloss (\S+) ", log)]
    check(losses and all(math.isfinite(v) for v in losses),
          f"probe losses missing or non-finite: {losses}")
    val = re.search(r" \* Acc@1 (\S+), Acc@5 (\S+)", log)
    check(val is not None, "probe logged no validation accuracy")
    acc1 = float(val.group(1).rstrip(","))
    check(0.0 <= acc1 <= 100.0, f"val Acc@1 out of range: {acc1}")
    say(f"probe: {len(losses)} steps, last loss {losses[-1]}, val Acc@1 {acc1}")


def embed(run: str) -> None:
    """The HTTP stack on ``<run>/last`` in a thread of this process."""
    from simclr_pytorch_distributed_tpu.serve import server as serve

    # the content-keyed cache is off: with it, image 0 of the later requests
    # would be answered from the first one and the pad-row check below
    # would compare a value with itself
    args = serve.build_parser().parse_args([
        "--ckpt", os.path.join(run, "last"), "--port", "0",
        "--cache_capacity", "0",
    ])
    with phase("embed"):
        engine, batcher, httpd = serve.build_stack(args)
        thread = serve.start_in_thread(httpd)
        url = f"http://127.0.0.1:{httpd.server_address[1]}/embed"
        size = engine.img_size
        images = np.random.default_rng(0).integers(
            0, 256, size=(13, size, size, 3), dtype=np.uint8
        )
        first_rows = []
        try:
            for n in (1, 8, 13):  # 13 crosses two buckets
                body = json.dumps({
                    "images_b64": base64.b64encode(images[:n].tobytes()).decode(),
                    "shape": [n, size, size, 3],
                }).encode()
                req = urllib.request.Request(
                    url, data=body, headers={"Content-Type": "application/json"}
                )
                t0 = time.perf_counter()
                with urllib.request.urlopen(req, timeout=600) as resp:
                    check(resp.status == 200, f"/embed {n}: HTTP {resp.status}")
                    reply = json.loads(resp.read())
                emb = np.asarray(reply["embeddings"], np.float32)
                check(emb.shape == (n, reply["dim"]) and reply["n"] == n,
                      f"/embed {n}: shape {emb.shape}, reply n={reply['n']} "
                      f"dim={reply['dim']}")
                check(bool(np.isfinite(emb).all()), f"/embed {n}: non-finite")
                first_rows.append(emb[0])
                say(f"/embed n={n}: 200, [{n}, {reply['dim']}], "
                    f"{time.perf_counter() - t0:.2f}s (compiles its bucket)")
        finally:
            httpd.shutdown()
            httpd.server_close()
            batcher.close()
            thread.join(timeout=30)
        check(not thread.is_alive(), "server thread did not stop")
    drift = max(float(np.abs(r - first_rows[0]).max()) for r in first_rows[1:])
    scale = float(np.abs(first_rows[0]).max())
    say(f"/embed image 0 across the three requests: max abs diff {drift:.3e} "
        f"(max abs value {scale:.3e}; the CPU cross-bucket contract of "
        f"tests/test_serve_engine.py is 1e-5)")
    # pad rows must not leak: image 0 embeds the same whatever shares its
    # batch. The three requests run three bucket programs, so equality is
    # to rounding, not bitwise; a leak (train-mode BN over pad rows) is O(1).
    # On the TPU fp32 convs run at default precision, inputs rounded to bf16
    # (half an ulp = 2e-3), so that is the bound there.
    bound = (2e-3 if jax.default_backend() == "tpu" else 1e-5) * max(1.0, scale)
    check(drift <= bound, f"pad rows leak into image 0: {drift} > {bound}")


def mesh_step_comparison(out: str, flags: list) -> None:
    """One ``make_fused_update`` step, same seed and global batch, on a
    one-device mesh against the all-device mesh: loss and gradient norm agree
    to fp32 reduction-order tolerance (tests/test_distributed.py's pattern).

    That tolerance means something only in fp32 arithmetic, so the steps run
    at ``highest`` matmul precision. At the TPU's default (fp32 operands
    rounded to bf16) the same pair differed by 4.2e-05 in the loss and
    2.3e-02 in the gradient norm (my chip run, PR 23): rounding noise that a
    reordered reduction re-draws, not a reduction order.
    """
    from simclr_pytorch_distributed_tpu import config as config_lib
    from simclr_pytorch_distributed_tpu.parallel.mesh import (
        create_mesh,
        shard_host_batch,
    )
    from simclr_pytorch_distributed_tpu.train import supcon

    cfg = config_lib.parse_supcon(flags + [
        "--dataset", "synthetic", "--workdir", out, "--trial", "mesh_cmp",
    ])
    steps = SYNTHETIC_TRAIN_IMAGES // cfg.batch_size
    rng = np.random.default_rng(cfg.seed)
    images = rng.integers(
        0, 256, size=(cfg.batch_size, cfg.size, cfg.size, 3), dtype=np.uint8
    )
    labels = rng.integers(0, 10, size=(cfg.batch_size,)).astype(np.int32)

    def one_step(devices, loss_impl=None):
        mesh = create_mesh(devices=devices)
        model, schedule, tx, state, step_cfg = supcon.build(
            cfg, steps, n_devices=len(devices)
        )
        # every step writes the gradient norm into its metrics
        step_cfg = dataclasses.replace(
            step_cfg, health=True, health_freq=1,
            loss_impl=loss_impl or step_cfg.loss_impl,
        )
        update = supcon.make_fused_update(
            model, tx, schedule, step_cfg, supcon.make_augment_config(cfg),
            mesh, state,
        )
        sh_images, sh_labels = shard_host_batch((images, labels), mesh)
        check(len(sh_images.sharding.device_set) == len(devices),
              f"batch lives on {len(sh_images.sharding.device_set)} devices, "
              f"mesh has {len(devices)}")
        new_state, metrics = update(
            state, sh_images, sh_labels, jax.random.key(cfg.seed)
        )
        for leaf in jax.tree.leaves(new_state.params):
            check(leaf.sharding.is_fully_replicated
                  and len(leaf.sharding.device_set) == len(devices),
                  f"a parameter is not replicated on {len(devices)} devices")
        loss, gnorm = float(metrics["loss"]), float(metrics["health_grad_norm"])
        say(f"step on {len(devices)} device(s), loss_impl {step_cfg.loss_impl}: "
            f"loss {loss!r}, grad norm {gnorm!r}")
        return loss, gnorm

    n = jax.device_count()
    with jax.default_matmul_precision("highest"):
        loss1, gnorm1 = one_step(jax.devices()[:1])
        loss_n, gnorm_n = one_step(jax.devices())
        check(math.isfinite(loss1) and math.isfinite(gnorm1),
              "non-finite reference step")
        loss_diff = abs(loss_n - loss1) / abs(loss1)
        gnorm_diff = abs(gnorm_n - gnorm1) / abs(gnorm1)
        say(f"{n} devices vs one: loss rel diff {loss_diff:.3e}, "
            f"grad norm rel diff {gnorm_diff:.3e}")
        if loss_diff > 2e-5 or gnorm_diff > 1e-3:
            # say which side is off before failing: the dense loss is plain
            # HLO that GSPMD partitions, with no kernel in it
            one_step(jax.devices(), loss_impl="dense")
    check(loss_diff <= 2e-5,
          f"loss on {n} devices {loss_n} != on one {loss1} (rtol 2e-5)")
    check(gnorm_diff <= 1e-3,
          f"grad norm on {n} devices {gnorm_n} != on one {gnorm1} (rtol 1e-3)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any backend; never prints an ok result")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit(
            f"chip_smoke needs a TPU; jax found {device.platform!r}. No CPU "
            "fallback (--rehearse walks the control flow at tiny sizes)."
        )
    check(jax.device_count() == args.chips,
          f"--chips {args.chips} but jax sees {jax.device_count()} devices")

    from simclr_pytorch_distributed_tpu.native.build import load as load_native
    from simclr_pytorch_distributed_tpu.train.supcon import enable_compile_cache

    versions()
    say(f"compile cache dir: {enable_compile_cache()}")
    say(f"native gather library: "
        f"{'loaded' if load_native() is not None else 'NOT loaded (numpy path)'}")
    flags = REHEARSAL_FLAGS if args.rehearse else LAUNCHER_FLAGS

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as out:
        if args.chips == 1:
            sync_retest()
            run = pretrain(out, flags, 1)
            probe(out, run, ["--model", "resnet18"] if args.rehearse else [])
            embed(run)
        else:
            pretrain(out, flags, args.chips)
            if device.platform == "tpu":
                peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
                # the replicated fp32 rn50 state alone is ~0.2 GB per device
                check(min(peaks) > 100e6,
                      f"a device held almost nothing after pretrain: {peaks}")
            with phase("mesh_step_comparison"):
                mesh_step_comparison(out, flags)

    if device.platform == "tpu":
        say(f"memory_stats of device 0 at the end: {device.memory_stats()}")
    result = {"device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": jax.device_count(),
    }}
    if args.rehearse:
        result = {"rehearsal": "passed", **result}
    else:
        result = {"ok": True, **result}
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
