#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``workloads/<cell>.json`` names a
configuration (``configs/<config>.json``: the launcher's flags, the sizes
the reference needs, source, precision, and the names of its plain reference,
of the adapter between the reference's names and the program's tree, of its
count of operations and of the step's program in the trace) and a traffic mix
(``traffic/<traffic>.json``: the data set's shape and the global batch), and
holds the cell's limits for ``correct``. ``BENCHMARK.json`` lists the
metrics; each per-layer metric is read by ``metrics/<name>.py``.

What a run does: make data and weights from the seed; wire the program's
pretrain driver as ``train.supcon.run`` wires it (mesh, ``EpochLoader``,
device-resident store, ``build``, ``make_fused_update``, telemetry session,
flight recorder); drive ``train.supcon.train_one_epoch`` through its first
flush window, which compiles and is set-up; hand the same update and state
to the measured window, which calls ``train_one_epoch`` epoch after epoch
until the clock runs out and ends in ``block_until_ready``; read the
device's memory; free the program's state; follow the first three steps
with the plain reference and compare. The last line of standard output is
the result.

The harness needs a TPU and never falls back. ``--rehearse`` walks the same
control flow at tiny sizes on whatever backend JAX has, for the builder and
the tests, and its line carries no device metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_ANCHOR = "bench_trace_anchor"
COMPILES = {"backend_s": 0.0, "programs": 0, "cache_hits": 0}


def say(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}", file=sys.stderr, flush=True)


def load_json(*rel):
    with open(os.path.join(HERE, *rel)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's file with its configuration and traffic files read in."""
    cell = load_json("workloads", f"{name}.json")
    cell["config_file"] = load_json("configs", f"{cell['config']}.json")
    cell["traffic_file"] = load_json("traffic", f"{cell['traffic']}.json")
    return cell


def listed_metrics(cell_name: str, trace: bool) -> list:
    """The metrics of ``BENCHMARK.json`` that this run reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = bench["per_layer" if trace else "end_to_end"]
    return [m for m in metrics if cell_name in m.get("workloads", [cell_name])]


def load_module(*rel):
    """A Python file of the benchmark, found by its name: a metric's reader,
    or the reference, adapter or operation count that a configuration names."""
    if len(rel) == 1:  # beside this file, which is on sys.path
        return importlib.import_module(rel[0][:-3])
    name = "bench_" + "_".join(rel)[:-3].replace(".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, *rel))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def load_reader(metric_name: str):
    return load_module("metrics", f"{metric_name}.py")


def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")["by_device_kind"]
    if device_kind not in table:
        raise SystemExit(f"benchmark/peaks.json has no entry for device kind "
                         f"{device_kind!r}; add one with its source")
    return table[device_kind]


def watch_compiles() -> None:
    """Seconds inside the backend's compile (or its cache read), programs
    compiled, persistent-cache hits: jax.monitoring's own events."""
    import jax

    if COMPILES.get("watching"):
        return
    COMPILES["watching"] = True

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILES["backend_s"] += duration
            COMPILES["programs"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            COMPILES["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def require_tpu(chips: int):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"the benchmark needs a TPU and JAX found no backend: {e}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found {devices[0].platform!r} "
                         "and there is no CPU fallback (--rehearse walks the flow)")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips and JAX sees {len(devices)}")
    return devices


def make_data(seed: int, traffic: dict, side: int):
    """The CIFAR-shaped train split from the seed: every image is a 4x4 grid
    of random colour tiles under pixel noise, so images have the coarse
    structure that crops, flips and colour jitter act on, all differ, and the
    contrastive task is not degenerate (pure pixel noise collapses every
    embedding onto one point, where gradients are rounding noise)."""
    import numpy as np

    rng = np.random.default_rng([seed, 0xC1FA])
    n, tile = traffic["n_images"], side // 4
    coarse = rng.integers(0, 208, size=(n, 4, 4, 3), dtype=np.uint8)
    images = np.repeat(np.repeat(coarse, tile, axis=1), tile, axis=2)
    images += rng.integers(0, 49, size=images.shape, dtype=np.uint8)  # 207 + 48 = 255
    labels = rng.integers(0, traffic["classes"], size=(n,)).astype(np.int32)
    return images, labels


def first_batches(images, seed: int, batch: int, steps: int = 3):
    """The rows of the first epoch's first ``steps`` steps, [steps, B, H, W, 3],
    by the recipe's sampler written down again: a permutation of the train
    split from numpy's ``default_rng(seed + epoch)``, epoch 1, cut into
    batches in order. The reference follows these, and never what the
    program's loader or store handed to the step."""
    import numpy as np

    order = np.random.default_rng(seed + 1).permutation(len(images))[: steps * batch]
    return images[order].reshape((steps, batch) + images.shape[1:])


def reference_hp(config: dict, traffic: dict, steps_per_epoch: int, size: int) -> dict:
    """What the reference needs, from the benchmark's own files alone."""
    r = config["recipe"]
    batch = traffic["global_batch"]
    return {
        "size": size, "mean": tuple(config["assumed"]["mean"]),
        "std": tuple(config["assumed"]["std"]), "bn_momentum": r["bn_momentum"],
        "temp": r["temp"], "base_temperature": r["base_temperature"],
        "grad_div": r["ngpu"], "momentum": r["momentum"],
        "weight_decay": r["weight_decay"], "learning_rate": r["learning_rate"],
        "lr_decay_rate": r["lr_decay_rate"], "epochs": r["epochs"],
        "warm": batch > r["warm_above_batch"], "warm_epochs": r["warm_epochs"],
        "warmup_from": r["warmup_from"], "steps_per_epoch": steps_per_epoch,
    }


class TimedUpdate:
    """The compiled update as ``train_one_epoch`` calls it, with the
    harness's bookkeeping around the call: it counts the steps, keeps what
    the comparison needs of the first three (the state after steps 1 and 3,
    the metric ring with the three losses), asks for the stop
    once the clock has run out, and starts and stops the profiler where a
    traced run wants it. The program sees the same compiled object and the
    same state in set-up and in the window."""

    def __init__(self, update_fn, snapshot):
        self.update_fn = update_fn
        self.snapshot = snapshot
        self.calls = 0
        self.kept = {}
        self.deadline = None
        self.trace_at = None  # (first call, calls, directory)
        self.tracing = False
        self.arg_specs = None

    def __call__(self, state, ring, images, labels, key):
        if self.calls == 0:
            import jax

            self.arg_specs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
                (state, ring, images, labels, key))
        if self.trace_at and not self.tracing and self.calls == self.trace_at[0]:
            self._start_trace()
        state, ring = self.update_fn(state, ring, images, labels, key)
        self.calls += 1
        if self.calls <= 3:
            # step k's loss sits in the ring's row (k - 1) % window
            first = self.calls == 1
            self.kept[self.calls] = self.snapshot((
                state.params if self.calls == 3 else None,
                state.opt_state if first else None,
                state.batch_stats if first else None, ring))
        if self.tracing and self.calls >= self.trace_at[0] + self.trace_at[1]:
            self.stop_trace()
        if self.deadline is not None and time.perf_counter() >= self.deadline:
            from simclr_pytorch_distributed_tpu.utils import preempt

            preempt.request()
        return state, ring

    def _start_trace(self):
        import jax

        from simclr_pytorch_distributed_tpu.utils import tracing

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_at[2], profiler_options=options)
        self.tracing = True
        # one instant on both clocks: the profiler's and the flight recorder's
        with jax.profiler.TraceAnnotation(TRACE_ANCHOR):
            tracing.event(TRACE_ANCHOR, track="bench")

    def stop_trace(self):
        if self.tracing:
            import jax

            jax.profiler.stop_trace()
            self.tracing = False
            self.trace_at = None


def memory_of(devices) -> dict:
    """Peak bytes on the fullest chip, from the counter that holds the
    program's temporaries: on this runtime they are reserved, not "in use"."""
    peak, limit, whole = 0, 0, []
    for d in devices:
        stats = d.memory_stats() or {}
        whole.append(stats)
        p = max(stats.get("peak_bytes_reserved", 0), stats.get("peak_bytes_in_use", 0))
        if p >= peak:
            peak, limit = p, stats.get("bytes_limit", 0)
    return {"peak_bytes": int(peak), "bytes_limit": int(limit), "stats": whole}


def drive(cell_name: str, seed: int, seconds: float, trace: bool, rehearse: bool = False,
          devices=None, flag_overrides=(), keep: dict = None) -> dict:
    """Set-up, window, reading, comparison. Returns the result line's dict.

    ``flag_overrides`` are appended to the configuration's flags and ``keep``,
    a dict, receives both sides' readings and a way to run the reference
    again: for ``control.py`` and the tests, never for a run of the benchmark.
    """
    import jax
    import jax.numpy as jnp

    import compare

    from simclr_pytorch_distributed_tpu import config as config_lib
    from simclr_pytorch_distributed_tpu import recipes as recipes_lib
    from simclr_pytorch_distributed_tpu.data import device_store
    from simclr_pytorch_distributed_tpu.data.pipeline import EpochLoader
    from simclr_pytorch_distributed_tpu.parallel.mesh import (
        create_mesh, epoch_buffer_sharding, replicated_sharding, state_sharding)
    from simclr_pytorch_distributed_tpu.train import supcon
    from simclr_pytorch_distributed_tpu.train.state import TrainState
    from simclr_pytorch_distributed_tpu.train.supcon_step import metric_keys
    from simclr_pytorch_distributed_tpu.utils import preempt, tracing
    from simclr_pytorch_distributed_tpu.utils.checkpoint import jit_copy_tree
    from simclr_pytorch_distributed_tpu.utils.logging_utils import setup_logging
    from simclr_pytorch_distributed_tpu.utils.obs import RunObservability
    from simclr_pytorch_distributed_tpu.utils.telemetry import TelemetrySession

    cell = load_cell(cell_name)
    config, traffic, chips = cell["config_file"], dict(cell["traffic_file"]), cell["chips"]
    # the configuration names its plain reference, the adapter between the
    # reference's names and the program's tree, and its count of operations
    reference, adapter, flops = (load_module(config[k]) for k in ("reference", "adapter", "flops"))
    devices = list(devices if devices is not None else jax.devices())[:chips]
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} devices and JAX sees {len(devices)}")
    size = config["architecture"]["image_size"]
    flags = list(config["flags"])
    recipe_stated = dict(config["recipe"])
    if rehearse:
        # tiny and gentle: at 16x16 and 16 rows a chip the recipe's learning
        # rate makes the third step chaotic, which says nothing of either side
        size = 16
        traffic.update(global_batch=16 * chips, n_images=16 * chips * 5)
        flags += ["--size", "16", "--print_freq", "3", "--learning_rate", "0.05"]
        recipe_stated["learning_rate"] = 0.05
    flags += list(flag_overrides)
    seed31 = seed % (2 ** 31 - 8)  # the program adds small offsets to its seed
    workdir = tempfile.mkdtemp(prefix="bench_run_")
    try:
        cfg = config_lib.parse_supcon(flags + [
            "--batch_size", str(traffic["global_batch"]), "--seed", str(seed31),
            "--dataset", "synthetic", "--workdir", workdir])
        cache_dir = supcon.enable_compile_cache()
        # every program of the run goes to the cache, the small ones too, so
        # that a second run of the cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        watch_compiles()
        setup_logging(cfg.save_folder, True)
        say(f"jax {jax.__version__}, devices {devices}, compile cache {cache_dir}")

        # --- the wiring of train.supcon.run, between flag parsing and the epoch loop
        mesh = create_mesh(devices=devices)
        images, labels = make_data(seed, traffic, config["assumed"]["source_side"]
                                   if not rehearse else size)
        loader = EpochLoader(images, labels, cfg.batch_size, base_seed=cfg.seed)
        steps_per_epoch = len(loader)
        obs = RunObservability(cfg, name="supcon")
        say("data made")
        store = device_store.make_store(cfg.data_placement, loader, mesh)
        if store is None:
            raise SystemExit("data_placement did not resolve to a device store")
        say("store resident")

        built = {}

        def abstract_state():
            model, schedule, tx, state, step_cfg = supcon.build(cfg, steps_per_epoch, mesh.size)
            built.update(model=model, schedule=schedule, tx=tx, step_cfg=step_cfg)
            return state

        state_shape = jax.eval_shape(abstract_state)  # build() traced, nothing run
        model, schedule, tx, step_cfg = (built[k] for k in ("model", "schedule", "tx", "step_cfg"))
        say(f"built: loss_impl {step_cfg.loss_impl}, steps per epoch {steps_per_epoch}, "
            f"global batch {cfg.batch_size}, warm {cfg.warm}")

        def fresh_state(key):
            """Weights from the seed in the reference's naming, laid out as
            the program's tree; everything else as ``create_train_state``."""
            params = adapter.to_program(
                reference.init_params(key, cfg.model, cfg.feat_dim), state_shape.params)
            return TrainState(
                step=jnp.zeros((), jnp.int32), params=params,
                batch_stats=jax.tree_util.tree_map_with_path(
                    lambda path, s: (jnp.ones if path[-1].key == "var" else jnp.zeros)(
                        s.shape, s.dtype), state_shape.batch_stats),
                opt_state=tx.init(params),
                record_norm_mean=jnp.zeros((), jnp.float32))

        weights_key = jax.random.key(seed31)
        state = jax.jit(fresh_state, out_shardings=state_sharding(mesh, state_shape))(weights_key)
        say("state made on the device")
        state, recipe = recipes_lib.attach_for_config(cfg, model, state, schedule=schedule)
        telemetry = TelemetrySession(
            cfg.print_freq,
            metric_keys(health=step_cfg.health, online_probe=step_cfg.online_probe,
                        extra=recipe.metric_keys),
            cfg.telemetry, watchdog=obs.watchdog, gauges=obs.gauges)
        update = TimedUpdate(
            supcon.make_fused_update(
                model, tx, schedule, step_cfg, supcon.make_augment_config(cfg), mesh, state,
                metric_ring=telemetry.ring, resident=True,
                window_batches=store.window_batches, probe=None, recipe=recipe),
            snapshot=jax.jit(lambda tree: jax.tree.map(jnp.copy, tree)))
        # no TensorBoard writer: importing it costs every run some ten seconds
        # of set-up, and train_one_epoch takes None (PERF.md, Open questions)
        tb = None
        float(schedule(0))  # run() evaluates the schedule eagerly at each epoch's end
        base_key = jax.random.key(cfg.seed + 1)
        position = {"epoch": 1, "step": 0}

        def run_epochs(until_flag_only: bool):
            """``run``'s epoch loop without its saves: train_one_epoch from the
            current position until the preemption flag stops it."""
            nonlocal state
            first = True
            while True:
                epoch, ss = position["epoch"], position["step"]
                if ss == 0:
                    jit_copy_tree(state)  # run()'s per-epoch crash backup
                obs.set_epoch(epoch)
                with tracing.span("epoch", track="main:epoch", epoch=epoch):
                    state, loss_avg, _, stopped_at = supcon.train_one_epoch(
                        epoch, loader, update, state, mesh, base_key, cfg, tb,
                        steps_per_epoch, start_step=ss, telemetry=telemetry, store=store,
                        compile_span=first and until_flag_only,
                        health_monitor=obs.health, gauges=obs.gauges)
                first = False
                if stopped_at is None:
                    position.update(epoch=epoch + 1, step=0)
                    logging.info("epoch %d, loss %.4f", epoch, loss_avg)
                    logging.info("learning rate %.6f",
                                 float(schedule((epoch - 1) * steps_per_epoch)))
                else:
                    position.update(step=stopped_at)
                if preempt.requested():
                    return

        # --- set-up: the first flush window through the window's own call
        preempt.request()
        run_epochs(until_flag_only=True)
        preempt.uninstall()  # clears the flag
        # the epoch-top backup once more, on a state that the update has made
        jax.block_until_ready(jit_copy_tree(state))
        warm_steps = update.calls
        setup_s = time.perf_counter() - T_START
        compiles_setup = dict(COMPILES)
        say(f"set-up {setup_s:.2f}s: {warm_steps} steps, backend compile "
            f"{COMPILES['backend_s']:.2f}s over {COMPILES['programs']} programs, "
            f"{COMPILES['cache_hits']} cache hits")

        # --- the measured window
        trace_dir = os.path.join(workdir, "profile")
        if trace:
            per = cell.get("trace", {})
            update.trace_at = (warm_steps + per.get("after_steps", 30),
                               per.get("steps", 30), trace_dir)
            if rehearse:
                update.trace_at = None
        tracing.event("bench_window_start", track="bench")
        t0 = time.perf_counter()
        update.deadline = t0 + seconds
        run_epochs(until_flag_only=False)
        jax.block_until_ready(state)
        t1 = time.perf_counter()
        tracing.event("bench_window_end", track="bench")
        update.stop_trace()
        preempt.uninstall()
        window_steps = update.calls - warm_steps
        window_s = t1 - t0
        compiled_in_window = COMPILES["programs"] - compiles_setup["programs"]
        say(f"window {window_s:.3f}s, {window_steps} steps, "
            f"{window_steps * cfg.batch_size / window_s:.1f} imgs/s, "
            f"{compiled_in_window} programs compiled inside it")

        # --- readings, then free the program's state
        memory = memory_of(devices) if devices[0].platform == "tpu" else {
            "peak_bytes": 0, "bytes_limit": 0, "stats": []}
        say(f"memory_stats per device: {memory['stats']}")
        telemetry.close()
        store.close()
        records = obs.recorder.snapshot() if obs.recorder is not None else []
        obs.close(exit_code=0)
        kept = update.kept
        kinds = None
        if trace:
            # what each operation of the trace is, from the compiled step's own
            # text: the same program again, out of the compile cache
            import trace_reduce

            kinds = trace_reduce.hlo_kinds(
                update.update_fn.lower(*update.arg_specs).compile().as_text())
            say(f"compiled step's text read: {len(kinds)} convolutions, kernels and collectives")
        ring_keys, ring_window = telemetry.ring.keys, telemetry.ring.window
        del state, store, update, loader, telemetry

        # --- the program's first three steps, under the reference's names
        hp = reference_hp(dict(config, recipe=recipe_stated), traffic, steps_per_epoch, size)
        repl = replicated_sharding(mesh)
        ref_params = jax.jit(lambda k: reference.init_params(k, cfg.model, cfg.feat_dim),
                             out_shardings=repl)(weights_key)
        program = program_readings(kept, ref_params, hp, ring_keys, ring_window,
                                   reference, adapter)
        del kept
        shardings = (repl, epoch_buffer_sharding(mesh, 5))
        fed = jax.device_put(first_batches(images, seed31, cfg.batch_size), shardings[1])
        reference_key = jax.random.key(seed31 + 1)  # the recipe's: seed + 1, folded with the step
        t_ref, c_ref = time.perf_counter(), dict(COMPILES)

        def follow(**how):
            return reference.trajectory(ref_params, fed, reference_key, cfg.model, hp,
                                        shardings=shardings, **how)

        ref = follow()
        table = compare.leaf_table(program, ref)
        if keep is not None:
            keep.update(program=program, reference=ref, follow=follow, table=table)
        say(f"reference: {time.perf_counter() - t_ref:.2f}s, of which backend compile "
            f"{COMPILES['backend_s'] - c_ref['backend_s']:.2f}s over "
            f"{COMPILES['programs'] - c_ref['programs']} programs, "
            f"{COMPILES['cache_hits'] - c_ref['cache_hits']} cache hits")
        numbers = compare.compared(program, ref, table)
        numbers["compiled_in_window"] = compiled_in_window
        correct, compared = compare.verdict(numbers, dict(cell["limits"], compiled_in_window=0))
        correct = correct and window_steps > 0
        say(f"losses program {program['losses']} reference {ref['losses']}")
        say("held to no limit: " + ", ".join(
            f"{k} {v:.3g}" if isinstance(v, float) else f"{k} {v}"
            for k, v in numbers.items() if k not in compared))

        # --- metrics
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": memory["peak_bytes"]}
        run = {
            "cell": cell, "config": config, "traffic": traffic, "size": size, "chips": chips,
            "window_s": window_s, "window_steps": window_steps, "setup_s": setup_s,
            "global_batch": cfg.batch_size, "images": window_steps * cfg.batch_size,
            "memory": memory, "records": records, "trace": None, "stretch": None,
            "peaks": None if rehearse else peaks_for(devices[0].device_kind),
            "step_program": config["step_program"], "kinds": kinds, "flops": flops,
        }
        breakdown = None
        if trace and not rehearse:
            breakdown = read_trace(trace_dir, run, device)
        metrics = {}
        if not rehearse:
            for m in listed_metrics(cell_name, trace):
                value = load_reader(m["name"]).read(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result = {"correct": bool(correct), "attempted": window_steps, "failed": 0,
                  "metrics": metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["compared"] = compared
        if rehearse:
            say("a rehearsal: tiny sizes, no device metric")
        for name, row in compared.items():
            say(f"compared {name}: {row['value']:.6g} (limit {row['limit']:.6g})")
        say(f"correct: {correct}")
        return result
    finally:
        try:
            import jax.profiler

            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 - no trace was running
            pass
        shutil.rmtree(workdir, ignore_errors=True)


def program_readings(kept, p0, hp, ring_keys, ring_window, reference, adapter):
    """What ``compare.py`` takes, read from the program's state and metric
    ring: the losses of steps 1-3; the first gradient from the momentum after
    step 1 (``trace = g + wd * p0`` on the first step); the parameters' change
    from those after step 3; the change of BN's running statistics over step
    1. Arrays stay on the device."""
    import jax
    import optax

    _, opt1, stats1, _ = kept[1]
    params3 = kept[3][0]
    trace = [s for s in jax.tree.leaves(opt1, is_leaf=lambda x: isinstance(x, optax.TraceState))
             if isinstance(s, optax.TraceState)]
    if len(trace) != 1:
        raise SystemExit("the optimizer state holds no single momentum trace to read "
                         "the first gradient from")

    def arrays(mom1, p3, stats1, p0):
        before = reference.init_running(p0)
        return {"grad": {k: mom1[k] - hp["weight_decay"] * p0[k] for k in p0},
                "change": {k: p3[k] - p0[k] for k in p0},
                "stats": {k: stats1[k] - before[k] for k in before}}

    out = jax.jit(arrays)(adapter.to_reference(trace[0].trace), adapter.to_reference(params3),
                          adapter.to_reference(stats1), p0)
    rings = jax.device_get([kept[k][3] for k in (1, 2, 3)])
    column = list(ring_keys).index("loss")
    # step k's loss sits in the ring's row (k - 1) % window
    return dict(out, losses=[float(rings[k][k % ring_window][column]) for k in range(3)])


def read_trace(trace_dir: str, run: dict, device: dict):
    """Reduce the profile to the plain form the readers take, the device's
    busy seconds, and the breakdown."""
    import trace_reduce as tr

    kinds = run["kinds"]
    trace = tr.load_xplane(trace_dir, host_events=(TRACE_ANCHOR,))
    planes = tr.device_planes(trace)[: run["chips"]]
    stretches = [tr.steady_stretch(p, run["step_program"]) for p in planes]
    if not planes or any(s is None for s in stretches):
        raise SystemExit("the trace holds no steady stretch of whole steps")
    run["trace"], run["planes"], run["stretches"] = trace, planes, stretches
    busy = [tr.busy_seconds(p, s[0], s[1]) for p, s in zip(planes, stretches)]
    device["busy_s"] = sum(busy) / len(busy)
    device["window_s"] = sum((s[1] - s[0]) / 1e9 for s in stretches) / len(stretches)
    # the chip with most idle time stands for the cell
    idle = [1.0 - b / ((s[1] - s[0]) / 1e9) for b, s in zip(busy, stretches)]
    worst = idle.index(max(idle))
    run["worst"] = worst
    plane, (t0, t1, _) = planes[worst], stretches[worst]
    by_kind = tr.seconds_by_kind(plane, t0, t1, kinds)
    say("device seconds of the stretch by kind: " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(by_kind.items(), key=lambda kv: -kv[1])))
    return {"device_ops": tr.top_ops(plane, t0, t1, kinds),
            "idle_gaps": gaps_by_host_span(tr, trace, plane, t0, t1, run["records"])}


def gaps_by_host_span(tr, trace, plane, t0, t1, records, least_s=20e-6):
    """The idle gaps of the stretch, summed by the flight-recorder span that
    was open on the host's main thread when each began; the anchor event,
    recorded on both clocks, ties the two."""
    anchor_prof = tr.find_host_event(trace, TRACE_ANCHOR)
    anchor_rec = next((r["ts"] for r in records if r["name"] == TRACE_ANCHOR), None)
    open_spans = []
    if anchor_prof is not None and anchor_rec is not None:
        for r in records:
            if r.get("ph") == "X" and r["track"].startswith("main:") and r["track"] != "main:epoch":
                start = anchor_prof + (r["ts"] - anchor_rec) * 1e9
                open_spans.append((start, start + r["dur"] * 1e9, f"{r['track']}/{r['name']}"))
    sums = {}
    for start, secs in tr.idle_gaps(plane, t0, t1):
        if secs < least_s:
            name = "gaps under 20us between operations"
        else:
            name = next((n for s, e, n in open_spans if s <= start < e),
                        "no span open (dispatch loop)")
        sums[name] = sums.get(name, 0.0) + secs
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:10]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never a device metric")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)  # an unknown cell fails before JAX starts
    if not args.rehearse:
        require_tpu(cell["chips"])
    result = drive(args.workload, args.seed, args.seconds, bool(args.trace), args.rehearse)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
