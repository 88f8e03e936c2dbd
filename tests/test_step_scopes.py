"""The step named from inside (PR 25): scopes in the compiled text, the
on-demand way to that text, and the driver loop's own counters and spans.

Three tiny REAL driver runs (resnet10, 8x8, 6-step epochs) feed every check:
sync and async telemetry with the registry as shipped (the async one under
``--trace_dir``), and one sync run with the registry's two hooks stubbed out,
the control for "an untraced run lowers nothing extra"."""

import importlib
import os
import sys

import jax
import numpy as np
import pytest

from simclr_pytorch_distributed_tpu.utils import profiling, tracing

pytestmark = pytest.mark.obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 8
OTHER_THREADS = ("telemetry:", "store:", "prefetch:")


@pytest.fixture(scope="module")
def scope_reduce():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        yield importlib.import_module("scope_reduce")
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmark"))


def _tiny_run(tmp, telemetry, trace, registry=True):
    """One 2-epoch supcon run; returns its recorder events (and the text)."""
    from simclr_pytorch_distributed_tpu import config as config_lib
    from simclr_pytorch_distributed_tpu.data import cifar as cifar_lib
    from simclr_pytorch_distributed_tpu.parallel import mesh as mesh_lib
    from simclr_pytorch_distributed_tpu.train import supcon as supcon_driver

    with pytest.MonkeyPatch.context() as mp:
        orig_synth = cifar_lib.synthetic_dataset
        mp.setattr(
            cifar_lib, "synthetic_dataset",
            lambda n=2048, num_classes=10, seed=0, size=32: orig_synth(
                n=200, num_classes=num_classes, seed=seed, size=SIZE),
        )
        mp.setattr(
            supcon_driver, "create_mesh",
            lambda devices=None, **kw: mesh_lib.create_mesh(
                devices=jax.devices()[:1] if devices is None else devices, **kw),
        )
        noted = []  # per call: is every leaf of the noted state committed?
        if not registry:
            mp.setattr(profiling, "register_step_program", lambda *a: None)
            mp.setattr(profiling, "note_step_signature", lambda *a: None)
        else:
            note = profiling.note_step_signature
            mp.setattr(profiling, "note_step_signature", lambda args: (
                noted.append(all(x.committed for x in jax.tree.leaves(args[:2]))),
                note(args))[1])
        profiling.clear_step_program()
        cfg = config_lib.SupConConfig(
            model="resnet10", dataset="synthetic", batch_size=32, epochs=2,
            learning_rate=0.05, cosine=True, save_freq=5, print_freq=2,
            size=SIZE, workdir=str(tmp), seed=0, method="SimCLR",
            telemetry=telemetry, data_placement="device",
            flight_recorder="on", loss_impl="fused",
            trace_dir=str(tmp / "profile") if trace else "",
            trace_start_step=7, trace_steps=3,
        )
        cfg = config_lib.finalize_supcon(cfg)
        supcon_driver.run(cfg)
    events = tracing.load_events_jsonl(
        os.path.join(cfg.save_folder, "events.jsonl"))
    return {"events": events, "trace_dir": cfg.trace_dir, "noted": noted}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    return {mode: _tiny_run(tmp_path_factory.mktemp(mode), mode, trace=mode == "async")
            for mode in ("sync", "async")}


@pytest.fixture(params=["sync", "async"])
def tiny_run(request, runs):
    return runs[request.param]


@pytest.fixture(scope="module")
def program_text(runs):
    """The compiled text of a tiny run's step, through the one public way:
    what ``step_program_text()`` gave the run's tracer."""
    with open(os.path.join(runs["async"]["trace_dir"],
                           profiling.STEP_PROGRAM_FILE)) as f:
        text = f.read()
    assert text.startswith("HloModule jit_ring_update")
    return text


# ------------------------------------------------ the text and its scopes


def test_no_text_before_a_program_is_registered():
    profiling.clear_step_program()
    assert profiling.step_program_text() is None
    profiling.register_step_program("f", jax.jit(lambda x: x + 1))
    assert profiling.step_program_text() is None  # registered, no call noted
    profiling.note_step_signature((np.ones(3, np.float32),))
    name, text = profiling.step_program_text()
    assert name == "f" and "HloModule" in text
    profiling.clear_step_program()


def test_signature_is_the_steady_calls_and_a_run_leaves_no_program(tiny_run):
    """Noted once a run, from the state and ring the compiling call returned
    (committed to the program's shardings: what every later call gets, not
    the fresh uncommitted state, for which run() compiles a second program);
    and a finished run leaves nothing for the next trainer of the process."""
    assert tiny_run["noted"] == [True]
    assert profiling.step_program_text() is None


@pytest.mark.parametrize("bucket", [
    "data", "aug", "stem", "layer1", "layer2", "layer3", "layer4", "head",
    "loss", "optimizer", "ring",
])
def test_every_bucket_appears_in_the_compiled_step(program_text, scope_reduce, bucket):
    scopes = scope_reduce.scope_map(program_text)
    assert bucket in {b for b, _ in scopes.values()}
    if bucket in ("stem", "layer1", "layer2", "layer3", "layer4", "head", "loss"):
        # differentiated: both passes are there, told apart by transpose(
        assert {d for b, d in scopes.values() if b == bucket} == {"fwd", "bwd"}


def test_instructions_with_an_op_name_fall_in_a_bucket(program_text, scope_reduce):
    named = [m.group(1) for m in scope_reduce._OP_NAME.finditer(program_text)
             if m.group(1).startswith("jit(")]
    inside = [n for n in named if scope_reduce.bucket_of(n) is not None]
    assert len(named) > 1000 and len(inside) >= 0.95 * len(named)
    # the op_name is all the metadata there is (enable_compile_cache): the
    # persistent cache is keyed on it, and a line shift must still hit
    assert "source_line" not in program_text
    # the fused loss's custom-VJP backward lands in the loss, not nowhere
    assert any(scope_reduce.bucket_of(n) == ("loss", "bwd") and "/while" in n
               for n in named)


def test_scope_names_are_the_constants_of_one_place(scope_reduce):
    from simclr_pytorch_distributed_tpu.train import supcon_step

    assert set(supcon_step.STEP_SCOPES) == set(scope_reduce._STEP_SCOPES)
    assert set(supcon_step.STEP_SCOPES) < set(scope_reduce.BUCKETS)
    assert scope_reduce.STEP_PROGRAM_FILE == profiling.STEP_PROGRAM_FILE


# ------------------------------------------- the driver loop's own record


def _main_thread(events):
    return sorted((e for e in events
                   if not e.get("track", "").startswith(OTHER_THREADS)),
                  key=lambda e: e["ts"])


def test_flush_boundary_carries_the_dispatch_counters(tiny_run):
    events = _main_thread(tiny_run["events"])
    boundaries = [e for e in events if e["name"] == "flush_boundary"]
    timed = [b for b in boundaries if "dispatch_s" in b["args"]]
    # 2 epochs x 3 windows of 2 steps; the tail boundaries have nothing timed
    assert len(timed) == 6
    assert all("dispatch_s" not in b["args"] for b in boundaries if b["args"]["steps"] == 0)
    for b in timed:
        a = b["args"]
        assert 0 < a["dispatch_min_s"] <= a["dispatch_max_s"] <= a["dispatch_s"]
        # boundary to boundary: since the end of whatever main-thread record
        # came before this span (the previous boundary's anchor, the epoch's
        # gather, the compile span)
        before = [e for e in events if e["ts"] + e.get("dur", 0.0) <= b["ts"]
                  and e["track"] != tracing.EPOCH_TRACK]
        earlier = [e for e in before if e["name"] in ("flush_boundary", "epoch_gather")]
        start = max(e["ts"] + e.get("dur", 0.0) for e in earlier)
        assert a["dispatch_s"] <= b["ts"] - start + 1e-4
    # the compiling call is main:compile's, not a dispatch: the first window
    # timed one step of its two
    first = timed[0]["args"]
    assert first["steps"] == 2 and first["dispatch_min_s"] == first["dispatch_max_s"]


def test_hot_loop_records_nothing_between_boundaries(tiny_run):
    """From a boundary's clock anchor to the next boundary's span: no record
    on the main thread but the run's one compile step with its compiles, each
    a program's trace, lowering and backend compile (and, under --trace_dir,
    the tracer's anchor)."""
    events = _main_thread(tiny_run["events"])
    allowed = {"first_step", "trace", "lower", "backend_compile", profiling.TRACE_ANCHOR}
    inside = False
    for e in events:
        if e["name"] == tracing.ANCHOR_EVENT and e["args"]["kind"] == "flush_boundary":
            inside = True
        elif e["name"] in ("flush_boundary", "drain_wait"):
            inside = False
        elif inside and e["name"] not in allowed and e["track"] != tracing.EPOCH_TRACK:
            # between epochs (after the drain) the driver records freely
            assert e["name"] in ("epoch_backup", "epoch_log", "epoch_gather",
                                 "checkpoint_save", "checkpoint_commit",
                                 "run_exit"), e


def test_drain_wait_never_overlaps_another_main_span(tiny_run):
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    try:
        trace_report = importlib.import_module("trace_report")
    finally:
        sys.path.remove(os.path.join(ROOT, "scripts"))
    events = tiny_run["events"]
    waits = [e for e in events if e["name"] == "drain_wait"]
    assert len(waits) == 2 and all(e["track"] == "main:flush" for e in waits)
    report = trace_report.build_report(events)
    assert report["consistency"]["ok"], report["consistency"]
    # the epoch-top backup and the end-of-epoch log stretch have spans too
    assert {"epoch_backup", "epoch_log"} <= {e["name"] for e in events}
    assert report["steady_state"]["dispatch_s"] > 0
    assert report["steady_state"]["rest_s"] >= -trace_report.OVERLAP_TOL_S


def _compiles_of_the_step(events):
    return sum(e["name"] == "backend_compile"
               and "ring_update" in e["args"].get("fun_name", "") for e in events)


def test_untraced_run_lowers_nothing_extra(tiny_run, tmp_path_factory):
    """The registry costs an untraced run no lowering and no compile: the
    step's program is compiled as often as with the registry's hooks
    stubbed out."""
    control = _tiny_run(tmp_path_factory.mktemp("control"), "sync",
                        trace=False, registry=False)
    assert _compiles_of_the_step(control["events"]) >= 1
    if not tiny_run["trace_dir"]:
        assert (_compiles_of_the_step(tiny_run["events"])
                == _compiles_of_the_step(control["events"]))
    else:  # the tracer asked for the text once, after its capture: at most
        # one more (none where the jit's own executable answers the lowering)
        extra = (_compiles_of_the_step(tiny_run["events"])
                 - _compiles_of_the_step(control["events"]))
        assert extra in (0, 1)


# --------------------------------------------------------- compile events


def test_compile_events_one_per_compile_none_without_recorder(tmp_path):
    tracing.forward_compile_events()
    tracing.forward_compile_events()  # once a process: no second listener
    x = np.ones(4, np.float32)

    def compiles(rec, name):
        return [e for e in rec.snapshot() if e["name"] == "backend_compile"
                and name in e["args"].get("fun_name", "")]

    rec = tracing.FlightRecorder()
    tracing.install(rec)
    try:
        def pr25_traced(v):
            return v * 3.0 + 1.0

        f = jax.jit(pr25_traced)
        f(x)
        first = compiles(rec, "pr25_traced")
        assert len(first) == 1 and first[0]["track"] == tracing.COMPILE_TRACK
        assert first[0]["args"]["duration_s"] >= 0
        f(x)  # the second call compiles nothing
        assert len(compiles(rec, "pr25_traced")) == 1
    finally:
        tracing.uninstall()
    g = jax.jit(lambda v: v * 5.0 - 1.0)
    g(x)  # no recorder installed: no work, and nothing lands anywhere
    assert len([e for e in rec.snapshot() if e["name"] == "backend_compile"]) == len(first)


# ------------------------------------------------ the operator's capture


def test_step_tracer_writes_anchor_and_program_text(runs, scope_reduce):
    from jax.profiler import ProfileData

    tiny_run = runs["async"]  # the one under --trace_dir

    text_path = os.path.join(tiny_run["trace_dir"], profiling.STEP_PROGRAM_FILE)
    with open(text_path) as f:
        text = f.read()
    assert "ring_update" in text and scope_reduce.scope_map(text)
    # the same instant on both clocks
    anchors = [e for e in tiny_run["events"] if e["name"] == profiling.TRACE_ANCHOR]
    assert len(anchors) == 1 and anchors[0]["track"] == "profile"
    xplanes = [os.path.join(r, f) for r, _, fs in os.walk(tiny_run["trace_dir"])
               for f in fs if f.endswith(".xplane.pb")]
    assert xplanes
    names = {ev.name for plane in ProfileData.from_file(xplanes[0]).planes
             for line in plane.lines for ev in line.events}
    assert profiling.TRACE_ANCHOR in names
