#!/usr/bin/env python
"""Does the background telemetry executor remove the per-window flush stall?

docs/PERF.md round 5 measured each MetricBuffer flush as a synchronous
batched D2H costing ~110 ms (~5.5 ms/step at the
recipe's ``print_freq 20``). The zero-sync path (device-side metric ring +
utils/telemetry.py background flush) claims to take that off the dispatch
thread. This script MEASURES it on a CPU proxy with an injected transfer
delay standing in for the slow link, rather than assuming it:

- both arms run the SAME compiled ring-mode fused update (one trace, shared
  by both — perfectly paired work);
- the ``sync`` arm runs every window job inline (``--telemetry sync``
  semantics: the dispatch thread eats D2H + delay);
- the ``async`` arm hands windows to the telemetry thread (``--telemetry
  async``) and only waits at the final ``drain()``;
- the injected delay wraps the ring's injectable ``device_get``
  (``--delay_ms``), the same hook the transfer-count tests instrument;
- arm order is ABBA within every round (PR 3's serve-sweep convention:
  machine drift moves medians more than the treatment), and the honest-sync
  rule holds — every timed arm ends by DRAINING the ring, so the fetched
  metric values are computed scalars that cannot exist until the steps ran.

Expectation: sync_ms_per_step - async_ms_per_step ~= delay/steps_per_window
(the async arm still pays the LAST window's delay at drain, amortized over
the whole arm). The committed artifact is docs/evidence/flush_ab_r6.json;
the chip expectation derived from it lives in docs/PERF.md ("Zero-sync
telemetry").

Usage: python scripts/flush_ab.py [--smoke] [--delay_ms N] [--json OUT]
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

from simclr_pytorch_distributed_tpu.ops.metrics import MetricRing  # noqa: E402
from simclr_pytorch_distributed_tpu.parallel.mesh import (  # noqa: E402
    create_mesh,
    replicated_sharding,
    shard_host_batch,
)
from simclr_pytorch_distributed_tpu.train.supcon_step import (  # noqa: E402
    METRIC_KEYS,
)
from simclr_pytorch_distributed_tpu.utils.telemetry import (  # noqa: E402
    TelemetrySession,
)

ARM_ORDER = ("sync", "async", "async", "sync")  # ABBA within every round


def build_output(device, delay_ms, window, windows, rounds_records):
    """Assemble the committed-artifact JSON from per-round arm timings.

    ``rounds_records``: one dict per round, ``{"sync": [ms_per_step, ...],
    "async": [...]}`` — two measurements per arm per round (the ABBA order).
    Pure so tests pin the schema without running the measurement.
    """
    all_sync = [v for r in rounds_records for v in r["sync"]]
    all_async = [v for r in rounds_records for v in r["async"]]
    sync_ms = statistics.median(all_sync)
    async_ms = statistics.median(all_async)
    return {
        "metric": "flush_ab_ms_per_step",
        "delay_ms": delay_ms,
        "window": window,
        "windows_per_arm": windows,
        "arm_order": "ABBA per round: " + ",".join(ARM_ORDER),
        "runs": rounds_records,
        "summary": {
            "sync_ms_per_step": round(sync_ms, 2),
            "async_ms_per_step": round(async_ms, 2),
            "stall_removed_ms_per_window": round((sync_ms - async_ms) * window, 1),
            "speedup": round(sync_ms / async_ms, 3) if async_ms > 0 else None,
        },
        "device": device,
        "note": (
            "paired CPU-proxy A/B: same compiled ring-mode update both arms; "
            "injected device_get delay stands in for the slow D2H link; each "
            "arm ends with drain() so every timed value is a computed scalar"
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
    ap.add_argument("--delay_ms", type=nonneg_float, default=None,
                    help="injected per-flush transfer delay; default 110 ms "
                         "(the round-5 measured flush cost), "
                         "400 ms under --smoke")
    ap.add_argument("--window", type=positive_int, default=None,
                    help="steps per flush window (the recipe's print_freq); "
                         "default 20, 10 under --smoke")
    ap.add_argument("--windows", type=positive_int, default=None,
                    help="windows per arm; default 4, 5 under --smoke")
    ap.add_argument("--rounds", type=positive_int, default=2,
                    help="ABBA rounds (2 measurements per arm per round)")
    ap.add_argument("--batch", type=positive_int, default=None,
                    help="default 64, 8 under --smoke")
    ap.add_argument("--size", type=positive_int, default=None,
                    help="default 16, 8 under --smoke")
    ap.add_argument("--model", default="resnet10")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU config (8px, 10-step windows) for tests "
                         "and the committed-artifact run")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    # --smoke picks the CPU-proxy shape (tuned so the injected stall is
    # comparable to the tiny-model compute: the effect must clear single-core
    # timer noise, ~±5 ms/step, by a wide margin, not hide inside it) but
    # only for flags the caller left unset — an explicit --delay_ms sweep
    # must not be silently overridden.
    smoke_defaults = dict(size=8, batch=8, window=10, windows=5,
                          delay_ms=400.0)
    full_defaults = dict(size=16, batch=64, window=20, windows=4,
                         delay_ms=110.0)
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
    model = SupConResNet(model_name=args.model, head="mlp", feat_dim=128)
    schedule = make_lr_schedule(learning_rate=0.1, epochs=10,
                                steps_per_epoch=100, cosine=True)
    tx = make_optimizer(schedule, momentum=0.9, weight_decay=1e-4)
    state = create_train_state(
        model, tx, jax.random.key(0),
        jnp.zeros((2, args.size, args.size, 3), jnp.float32),
    )
    step_cfg = SupConStepConfig(
        method="SimCLR", temperature=0.5, epochs=10, steps_per_epoch=100,
        grad_div=1.0, loss_impl="dense",
    )
    # one trace shared by BOTH arms: write-side columns come from this ring,
    # flush-side rings below only need the same (window, keys)
    ring_spec = MetricRing(args.window, METRIC_KEYS)
    update = make_fused_update(
        model, tx, schedule, step_cfg, AugmentConfig(size=args.size), mesh,
        state, metric_ring=ring_spec,
    )

    rng = np.random.default_rng(0)
    images = rng.integers(
        0, 256, size=(args.batch, args.size, args.size, 3), dtype=np.uint8
    )
    labels = rng.integers(0, 10, size=(args.batch,)).astype(np.int32)
    sh_images, sh_labels = shard_host_batch((images, labels), mesh)
    base_key = jax.random.key(42)
    repl = replicated_sharding(mesh)
    delay_s = args.delay_ms / 1e3

    def delayed_get(x):
        time.sleep(delay_s)
        return jax.device_get(x)

    gstep = [int(state.step)]

    def run_arm(mode, state):
        session = TelemetrySession(
            args.window, METRIC_KEYS, mode, device_get=delayed_get
        )
        sink = []
        ring_buf = session.init_buffer(repl)
        t0 = time.perf_counter()
        for w in range(args.windows):
            for _ in range(args.window):
                state, ring_buf = update(
                    state, ring_buf, sh_images, sh_labels, base_key
                )
                session.append(w, gstep[0])
                gstep[0] += 1
            session.submit_window(ring_buf, sink.extend)
        session.drain()  # computed-scalar materialization: the honest sync
        dt = time.perf_counter() - t0
        session.close()
        assert len(sink) == args.windows * args.window
        assert all(np.isfinite(m["loss"]) for _, m in sink)
        return state, dt * 1e3 / (args.windows * args.window)

    # warmup: compile + ONE FULL DISCARDED ARM (PR 3's discarded-warm-window
    # convention) — the first measured windows otherwise carry allocator /
    # code-cache settling that lands entirely on whichever arm runs first
    state, warm_ms = run_arm("sync", state)
    print(json.dumps({"warmup_discarded_ms_per_step": round(warm_ms, 2)}),
          flush=True)

    rounds_records = []
    for rnd in range(args.rounds):
        record = {"sync": [], "async": []}
        for mode in ARM_ORDER:
            state, ms = run_arm(mode, state)
            record[mode].append(round(ms, 2))
            print(json.dumps({"round": rnd, "arm": mode,
                              "ms_per_step": round(ms, 2)}), flush=True)
        rounds_records.append(record)

    out = build_output(
        jax.devices()[0].device_kind, args.delay_ms, args.window,
        args.windows, rounds_records,
    )
    print(json.dumps(out))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    main()
