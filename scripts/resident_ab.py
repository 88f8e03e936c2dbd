#!/usr/bin/env python
"""Does device-resident data placement remove the per-step H2D from the loop?

docs/PERF.md round 5 measured the production put-then-dispatch driver loop at
64.9-71.0 ms/step against a stable 64.6-65.2 ms resident-batch floor:
the per-step uint8 transfer cost a volatile 0-10 ms there. ``--data_placement device``
(data/device_store.py) claims to reach the measured floor by shipping only an
int32 index vector per EPOCH and slicing every batch out of an HBM-resident
shuffled buffer. This script MEASURES that on a CPU proxy instead of assuming
it, and PROVES the placement swap is free (bit-identical batches):

- both arms run the same model/step config; the ``host`` arm is the
  production loop shape (EpochLoader gather -> ``shard_host_batch`` ->
  dispatch), the ``device`` arm is the resident loop (one index upload +
  compiled shuffle-gather per epoch, then dispatch-only);
- on CPU the real H2D is ~free AND dispatch is asynchronous, so a bare
  injected sleep would hide behind the in-flight step — the opposite of the
  measured link, which SERIALIZED transfers against compute (that
  serialization is the whole 0-10 ms/step penalty). The proxy therefore
  models the serialized stream explicitly: before paying the injected
  ``--h2d_delay_ms`` transfer delay, the arm fences the in-flight step
  (``block_until_ready``), so one step costs compute + transfer exactly as
  on the serialized link. The host arm pays that fence+delay once per STEP
  at ``shard_host_batch``; the device arm once per EPOCH at the index
  upload (via the store's injectable ``index_put``, the same hook the
  transfer-count tests instrument) and is otherwise dispatch-only;
- arm order is ABBA within every round after one full discarded warm arm of
  EACH kind (two compiled programs — compile/settling must land on neither
  measured arm), and the honest-sync rule holds: every timed arm ends with a
  host readback of a COMPUTED loss scalar, which cannot exist until the
  steps actually ran;
- before any timing, an equivalence pass byte-compares every step of two
  device epochs (including a mid-epoch slice) against the host loader —
  ``equivalence_ok`` in the artifact is the bit-identity contract.

Expectation: host_ms - device_ms ~= delay * (1 - 1/steps_per_epoch) (the
device arm still pays one index-upload delay per epoch). The chip
expectation lives in docs/PERF.md ("Device-resident data pipeline").

Usage: python scripts/resident_ab.py [--smoke] [--h2d_delay_ms N] [--json OUT]
"""

import argparse
import json
import os
import statistics
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from simclr_pytorch_distributed_tpu.data import device_store  # noqa: E402
from simclr_pytorch_distributed_tpu.data.pipeline import EpochLoader  # noqa: E402
from simclr_pytorch_distributed_tpu.parallel.mesh import (  # noqa: E402
    create_mesh,
    shard_host_batch,
)

ARM_ORDER = ("host", "device", "device", "host")  # ABBA within every round


def build_output(device, h2d_delay_ms, steps_per_epoch, epochs_per_arm,
                 rounds_records, equivalence):
    """Assemble the committed-artifact JSON from per-round arm timings.

    ``rounds_records``: one dict per round, ``{"host": [ms_per_step, ...],
    "device": [...]}`` — two measurements per arm per round (the ABBA
    order). Pure so tests pin the schema without running the measurement.
    """
    all_host = [v for r in rounds_records for v in r["host"]]
    all_device = [v for r in rounds_records for v in r["device"]]
    host_ms = statistics.median(all_host)
    device_ms = statistics.median(all_device)
    return {
        "metric": "resident_ab_ms_per_step",
        "h2d_delay_ms": h2d_delay_ms,
        "steps_per_epoch": steps_per_epoch,
        "epochs_per_arm": epochs_per_arm,
        "arm_order": "ABBA per round: " + ",".join(ARM_ORDER),
        "runs": rounds_records,
        "equivalence": equivalence,
        "summary": {
            "host_ms_per_step": round(host_ms, 2),
            "device_ms_per_step": round(device_ms, 2),
            "transfer_removed_ms_per_step": round(host_ms - device_ms, 2),
            "speedup": round(host_ms / device_ms, 3) if device_ms > 0 else None,
        },
        "device": device,
        "note": (
            "paired CPU-proxy A/B: host arm = production per-step "
            "gather+device_put loop, device arm = HBM-resident epoch buffer "
            "(one index upload/epoch); the injected h2d delay models the "
            "SERIALIZED host link (fence in-flight step, then pay the "
            "delay — PERF.md round-5 measured that serialization) and is "
            "paid per step (host) vs per epoch (device); each arm ends "
            "with a computed-loss readback; equivalence = byte-equal "
            "batches, the bit-identity contract"
        ),
    }


def main(argv=None):
    def positive_int(s):
        v = int(s)
        if v < 1:
            raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
        return v

    def nonneg_float(s):
        v = float(s)
        if v < 0:
            raise argparse.ArgumentTypeError(f"must be >= 0, got {v}")
        return v

    ap = argparse.ArgumentParser()
    ap.add_argument("--h2d_delay_ms", type=nonneg_float, default=None,
                    help="injected per-transfer delay; default 50 ms, 200 ms "
                         "under --smoke (like flush_ab, the injected stall "
                         "must dominate the tiny-model compute so the "
                         "effect clears 1-core timer/contention noise, "
                         "~25 ms/step observed, by a wide margin)")
    ap.add_argument("--steps", type=positive_int, default=None,
                    help="steps per epoch; default 20, 8 under --smoke")
    ap.add_argument("--epochs", type=positive_int, default=None,
                    help="epochs per timed arm; default 3, 2 under --smoke")
    ap.add_argument("--rounds", type=positive_int, default=2,
                    help="ABBA rounds (2 measurements per arm per round)")
    ap.add_argument("--batch", type=positive_int, default=None,
                    help="global batch; default 64, 8 under --smoke")
    ap.add_argument("--size", type=positive_int, default=None,
                    help="default 16, 8 under --smoke")
    ap.add_argument("--model", default="resnet10")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU config for tests and the committed-"
                         "artifact run")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    # --smoke picks the CPU-proxy shape (the injected per-step penalty must
    # clear single-core timer noise by a wide margin) but only for flags the
    # caller left unset — an explicit sweep value is never overridden.
    smoke_defaults = dict(size=8, batch=8, steps=8, epochs=2,
                          h2d_delay_ms=200.0)
    full_defaults = dict(size=16, batch=64, steps=20, epochs=3,
                         h2d_delay_ms=50.0)
    for k, v in (smoke_defaults if args.smoke else full_defaults).items():
        if getattr(args, k) is None:
            setattr(args, k, v)

    import jax.numpy as jnp

    from simclr_pytorch_distributed_tpu.models import SupConResNet
    from simclr_pytorch_distributed_tpu.ops.augment import AugmentConfig
    from simclr_pytorch_distributed_tpu.ops.schedules import make_lr_schedule
    from simclr_pytorch_distributed_tpu.train.state import (
        create_train_state,
        make_optimizer,
    )
    from simclr_pytorch_distributed_tpu.train.supcon import make_fused_update
    from simclr_pytorch_distributed_tpu.train.supcon_step import SupConStepConfig

    mesh = create_mesh(devices=jax.devices()[:1])
    delay_s = args.h2d_delay_ms / 1e3

    # dataset sized to exactly steps*batch rows (plus a drop_last remainder
    # so truncation is exercised), same rng recipe as the committed benches
    rng = np.random.default_rng(0)
    n = args.steps * args.batch + args.batch // 2
    images = rng.integers(
        0, 256, size=(n, args.size, args.size, 3), dtype=np.uint8
    )
    labels = rng.integers(0, 10, size=(n,)).astype(np.int32)
    loader = EpochLoader(images, labels, args.batch, base_seed=7)
    assert loader.steps_per_epoch == args.steps

    def delayed_index_put(idx):
        time.sleep(delay_s)  # the device arm's ONE per-epoch transfer
        return jax.device_put(idx)

    store = device_store.DeviceStore(loader, mesh, index_put=delayed_index_put)

    model = SupConResNet(model_name=args.model, head="mlp", feat_dim=128)
    schedule = make_lr_schedule(learning_rate=0.1, epochs=10,
                                steps_per_epoch=args.steps, cosine=True)
    tx = make_optimizer(schedule, momentum=0.9, weight_decay=1e-4)

    def fresh_state():
        return create_train_state(
            model, tx, jax.random.key(0),
            jnp.zeros((2, args.size, args.size, 3), jnp.float32),
        )

    step_cfg = SupConStepConfig(
        method="SimCLR", temperature=0.5, epochs=10,
        steps_per_epoch=args.steps, grad_div=1.0, loss_impl="dense",
    )
    aug_cfg = AugmentConfig(size=args.size)
    # scalar-mode updates (metric_ring=None): the loop shape under test is
    # the DATA path; telemetry stays out of both arms identically
    update_host = make_fused_update(
        model, tx, schedule, step_cfg, aug_cfg, mesh, fresh_state()
    )
    update_res = make_fused_update(
        model, tx, schedule, step_cfg, aug_cfg, mesh, fresh_state(),
        resident=True,
    )
    base_key = jax.random.key(42)

    # ---- equivalence pass (bit-identity, before any timing) -------------
    checked = 0
    mid = args.steps // 2
    mid_ok = True
    for epoch in (1, 2):
        ep_imgs, ep_labs = store.epoch_buffers(epoch)
        dev_imgs, dev_labs = np.asarray(ep_imgs), np.asarray(ep_labs)
        for s, (h_imgs, h_labs) in enumerate(loader.epoch(epoch)):
            if not (np.array_equal(dev_imgs[s], h_imgs)
                    and np.array_equal(dev_labs[s], h_labs)):
                raise SystemExit(
                    f"placement equivalence BROKEN at epoch {epoch} step {s}"
                )
            checked += 1
        # the mid-epoch resume contract is a slice-offset shift: the buffer
        # row at the resume position IS the loader's batch at that step
        resumed = list(loader.epoch(epoch, start_step=mid))
        mid_ok = mid_ok and np.array_equal(dev_imgs[mid], resumed[0][0])
    equivalence = {
        "equivalence_ok": bool(checked == 2 * args.steps and mid_ok),
        "steps_compared": checked,
        "epochs": 2,
        "mid_epoch_resume_checked": True,
    }
    print(json.dumps({"equivalence": equivalence}), flush=True)

    # ---- timing ---------------------------------------------------------
    epoch_counter = [0]  # monotonically fresh epochs: every arm reshuffles

    def run_arm(mode, state):
        t0 = time.perf_counter()
        for _ in range(args.epochs):
            epoch_counter[0] += 1
            epoch = epoch_counter[0]
            if mode == "device":
                # ONE serialized transfer per epoch (the index upload
                # inside epoch_buffers -> delayed_index_put); fence first —
                # same serialized-stream rule as the host arm's transfers
                jax.block_until_ready(state)
                ep_imgs, ep_labs = store.epoch_buffers(epoch)
                for _ in range(args.steps):
                    state, metrics = update_res(
                        state, ep_imgs, ep_labs, base_key
                    )
            else:
                for h_imgs, h_labs in loader.epoch(epoch):
                    # serialized-link model (module docstring): the link
                    # runs transfer and compute on ONE stream, so the
                    # injected transfer delay cannot start until the
                    # in-flight step retires
                    jax.block_until_ready(state)
                    time.sleep(delay_s)
                    batch = shard_host_batch((h_imgs, h_labs), mesh)
                    state, metrics = update_host(
                        state, batch[0], batch[1], base_key
                    )
        # honest sync: a computed scalar cannot exist until the steps ran
        assert np.isfinite(float(metrics["loss"]))
        dt = time.perf_counter() - t0
        return state, dt * 1e3 / (args.epochs * args.steps)

    # warmup: compile + ONE FULL DISCARDED ARM OF EACH KIND (two compiled
    # programs; allocator/code-cache settling must not land on a timed arm)
    state = fresh_state()
    state, warm_host = run_arm("host", state)
    state, warm_dev = run_arm("device", state)
    print(json.dumps({"warmup_discarded_ms_per_step":
                      {"host": round(warm_host, 2),
                       "device": round(warm_dev, 2)}}), flush=True)

    rounds_records = []
    for rnd in range(args.rounds):
        record = {"host": [], "device": []}
        for mode in ARM_ORDER:
            state, ms = run_arm(mode, state)
            record[mode].append(round(ms, 2))
            print(json.dumps({"round": rnd, "arm": mode,
                              "ms_per_step": round(ms, 2)}), flush=True)
        rounds_records.append(record)

    out = build_output(
        jax.devices()[0].device_kind, args.h2d_delay_ms, args.steps,
        args.epochs, rounds_records, equivalence,
    )
    print(json.dumps(out))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    main()
