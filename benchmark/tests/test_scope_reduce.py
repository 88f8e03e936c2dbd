"""Device time by scope, on a module text written by hand and on the recorded
form of a trace (``timeline.json``), and every reader PR 25 adds: None on a
rehearsal's ``run``, a number on the fixture."""

import json
import os

import pytest

import run as harness
import scope_reduce as sr
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
P = "jit(ring_update)"

# the compiled step's text, cut down: a conv fusion of the forward pass and
# one of the backward pass in layer1, an aug fusion, an optimizer fusion, a
# fusion with no metadata whose body votes (two loss instructions against one
# of the ring), an instruction with none, and a parameter
HLO = f"""
HloModule jit_ring_update

%fused_computation.1 (p0: f32[8,4,4,16]) -> f32[8,4,4,16] {{
  %p0 = f32[8,4,4,16]{{3,2,1,0}} parameter(0)
  ROOT %convolution.9 = f32[8,4,4,16]{{3,2,1,0}} convolution(%p0, %p0), metadata={{op_name="{P}/jvp(SupConResNet)/encoder/layer1_block0/Conv_0/conv_general_dilated" source_file="resnet.py" source_line=1}}
}}

%fused_computation.7 (p0: f32[8,16]) -> f32[8,16] {{
  %p0 = f32[8,16]{{1,0}} parameter(0)
  %exp.1 = f32[8,16]{{1,0}} exponential(%p0), metadata={{op_name="{P}/jvp(loss)/exp"}}
  %log.1 = f32[8,16]{{1,0}} log(%exp.1), metadata={{op_name="{P}/transpose(jvp(loss))/log"}}
  %sub.1 = f32[8,16]{{1,0}} subtract(%log.1, %p0), metadata={{op_name="{P}/transpose(jvp(loss))/sub"}}
  ROOT %multiply.4 = f32[8,16]{{1,0}} multiply(%sub.1, %p0), metadata={{op_name="{P}/ring/mul"}}
}}

ENTRY %main.1 (a: f32[8,4,4,16], z: f32[8,16]) -> f32[8,16] {{
  %a = f32[8,4,4,16]{{3,2,1,0}} parameter(0), metadata={{op_name="state.params['encoder']['layer2_block0']['Conv_0']['kernel']"}}
  %z = f32[8,16]{{1,0}} parameter(1)
  %fusion.1 = f32[8,4,4,16]{{3,2,1,0}} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{P}/jvp(SupConResNet)/encoder/layer1_block0/Conv_0/conv_general_dilated"}}
  %fusion.2 = f32[8,4,4,16]{{3,2,1,0}} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{P}/transpose(jvp(SupConResNet))/encoder/layer1_block0/Conv_0/conv_general_dilated"}}
  %fusion.3 = f32[8,16]{{1,0}} fusion(%z), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="{P}/aug/vmap(jit(_uniform))/mul"}}
  %all-reduce.3 = f32[8,16]{{1,0}} all-reduce(%z), replica_groups={{{{0,1}}}}, to_apply=%add, metadata={{op_name="{P}/optimizer/jit(floor_divide)/div"}}
  %fusion.7 = f32[8,16]{{1,0}} fusion(%all-reduce.3), kind=kLoop, calls=%fused_computation.7
  ROOT %custom-call.2 = f32[8,16]{{1,0}} custom-call(%fusion.7), custom_call_target="tpu_custom_call"
}}
"""


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "timeline.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("op_name, where", [
    (f"{P}/jvp(SupConResNet)/encoder/conv1/conv_general_dilated", ("stem", "fwd")),
    (f"{P}/transpose(jvp(SupConResNet))/encoder/bn1/mul", ("stem", "bwd")),
    (f"{P}/jvp(SupConResNet)/encoder/jit(relu)/max", ("stem", "fwd")),
    (f"{P}/jvp(SupConResNet)/encoder/layer3_block5/bn2/add", ("layer3", "fwd")),
    (f"{P}/transpose(jvp(SupConResNet))/proj_head/fc1/dot_general", ("head", "bwd")),
    (f"{P}/data/jit(_threefry_fold_in)/xor", ("data", "fwd")),
    (f"{P}/transpose(jvp(loss))/while/body/dot_general", ("loss", "bwd")),
    # the innermost scope decides: a recipe's second forward inside its loss
    (f"{P}/jvp(loss)/SupConResNet/encoder/layer2_block0/Conv_0/conv", ("layer2", "fwd")),
    (f"{P}/jit(loss)/mul", None),  # a jitted function's own name is no scope
    (f"{P}/add", None),
    ("state.params['encoder']['layer2_block0']['Conv_0']['kernel']", None),
])
def test_bucket_of_an_op_name(op_name, where):
    assert sr.bucket_of(op_name) == where


def test_scope_map_of_the_module_text():
    assert sr.scope_map(HLO) == {
        "convolution.9": ("layer1", "fwd"), "fusion.1": ("layer1", "fwd"),
        "fusion.2": ("layer1", "bwd"), "fusion.3": ("aug", "fwd"),
        "all-reduce.3": ("optimizer", "fwd"),
        "exp.1": ("loss", "fwd"), "log.1": ("loss", "bwd"), "sub.1": ("loss", "bwd"),
        "multiply.4": ("ring", "fwd"),
        "fusion.7": ("loss", "bwd"),  # no metadata of its own: its body's majority
    }  # custom-call.2, the parameters: in no bucket


def test_seconds_by_scope_sum_to_the_busy_seconds(trace):
    p0 = tr.device_planes(trace)[0]
    t0, t1, steps = tr.steady_stretch(p0, "ring_update")
    by_scope = sr.seconds_by_scope(p0, t0, t1, sr.scope_map(HLO))
    # chip 0, [100, 300] us: fusion.1 100-140 and 200-240; all-reduce.3 owns
    # 140-150 of its 140-160 (fusion.7 starts at 150 and started last) and all
    # of 240-260; fusion.7 150-180 and 260-280; custom-call.2 180-185, 280-285
    assert by_scope == {
        ("layer1", "fwd"): pytest.approx(80e-6), ("optimizer", "fwd"): pytest.approx(30e-6),
        ("loss", "bwd"): pytest.approx(50e-6), (sr.UNATTRIBUTED, ""): pytest.approx(10e-6)}
    assert sum(by_scope.values()) == tr.busy_seconds(p0, t0, t1)  # exactly
    assert sr.bucket_seconds(by_scope, sr.ENCODER, "bwd") == 0.0
    assert sr.bucket_seconds(by_scope, ("loss", "optimizer")) == pytest.approx(80e-6)
    assert "layer1" in sr.table(by_scope, tr.busy_seconds(p0, t0, t1), steps)


def test_an_operation_inside_another_owns_its_time():
    """A ``while`` with its body's operations nested in it: the body owns its
    time, the ``while`` what is left, and nothing is counted twice."""
    plane = {"name": "/device:TPU:0", "lines": [{"name": tr.OPS_LINE, "events": [
        ["%while.1 = (s32[]) while(%t)", 0.0, 100.0, {}],
        ["%fusion.1 = f32[] fusion(%a)", 0.0, 30.0, {}],
        ["%fusion.3 = f32[] fusion(%a)", 40.0, 30.0, {}],
        ["%fusion.2 = f32[] fusion(%a)", 120.0, 10.0, {}]]}]}
    scopes = dict(sr.scope_map(HLO), **{"while.1": ("ring", "fwd")})
    by_scope = sr.seconds_by_scope(plane, 0.0, 125.0, scopes)
    assert by_scope == {
        ("layer1", "fwd"): pytest.approx(30e-9), ("aug", "fwd"): pytest.approx(30e-9),
        ("ring", "fwd"): pytest.approx(40e-9), ("layer1", "bwd"): pytest.approx(5e-9)}
    assert sum(by_scope.values()) == pytest.approx(tr.busy_seconds(plane, 0.0, 125.0))


# ------------------------------------------------------- the operator's CLI


@pytest.mark.parametrize("text, says", [
    (HLO, "under instructions the text does not hold: 0.00% of the busy time"),
    # a text that is not the traced program's: custom-call.2 is not in it
    ("\n".join(ln for ln in HLO.splitlines() if "%custom-call.2" not in ln),
     f"under instructions the text does not hold: {10 / 170:.2%} of the busy time"),
    (HLO.replace("HloModule jit_ring_update", "HloModule other"), None),
], ids=["the_program_s_text", "another_text", "no_header"])
def test_cli_prints_the_table_and_what_the_text_lacks(text, says, trace, tmp_path, monkeypatch,
                                                      capsys):
    (tmp_path / sr.STEP_PROGRAM_FILE).write_text(text)
    monkeypatch.setattr(tr, "load_xplane", lambda path: trace if path == str(tmp_path) else None)
    if says is None:
        with pytest.raises(SystemExit, match="no 'HloModule jit_<program>' line"):
            sr.main([str(tmp_path)])
        return
    assert sr.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    # the program's name comes from the text's own header: no flag
    assert "/device:TPU:0: 2 steps of ring_update, 0.100 ms a step" in out
    assert says in out
    layer1 = next(ln.split() for ln in out.splitlines() if ln.startswith("layer1"))
    assert layer1[1:3] == ["0.040", "0.000"]
    with pytest.raises(SystemExit):
        sr.main([str(tmp_path), "--program", "ring_update"])


# ------------------------------------------------------------- the readers

NEW = [m for m in json.load(open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                              "BENCHMARK.json")))["per_layer"]
       if m["name"] not in (
           "step.mfu.pretrain", "device.idle_share.pretrain", "device.peak_mem_share",
           "encoder.conv_ms_per_step", "encoder.conv_roofline_share",
           "loss.kernel_ms_per_step", "driver.host_phase_ms_per_step")]


def rehearsal_run():
    """What ``run.drive`` hands the readers after a rehearsal: no trace, no
    window marks that a reader of spans could use but the two events."""
    return {"records": [], "trace": None, "stretch": None, "kinds": None,
            "window_steps": 7, "window_s": 1.0}


def fixture_run(trace):
    planes = tr.device_planes(trace)
    span = lambda name, ts, dur, **a: {"name": name, "track": "main:flush", "ph": "X",  # noqa: E731
                                       "ts": ts, "dur": dur, "args": a}
    mark = lambda name, ts: {"name": name, "track": "bench", "ph": "i", "ts": ts}  # noqa: E731
    return {
        "planes": planes, "stretches": [tr.steady_stretch(p, "ring_update") for p in planes],
        "worst": 0, "window_steps": 20, "window_s": 2.0,
        "records": [
            span("flush_boundary", 0.5, 0.01, steps=10, dispatch_s=0.9, dispatch_min_s=0.002,
                 dispatch_max_s=0.2),  # before the window: not read
            mark("bench_window_start", 1.0),
            span("flush_boundary", 1.5, 0.01, steps=10, dispatch_s=0.4, dispatch_min_s=0.001,
                 dispatch_max_s=0.1),
            span("flush_boundary", 2.0, 0.01, steps=10, dispatch_s=0.6, dispatch_min_s=0.0005,
                 dispatch_max_s=0.3),
            span("flush_boundary", 2.1, 0.001, steps=0),  # an epoch's tail: nothing timed
            span("drain_wait", 2.2, 0.3, step=19),
            mark("bench_window_end", 3.0),
            span("drain_wait", 3.5, 0.7, step=29)]}


EXPECTED = {  # on the fixture: 2 steps in the stretch, 170 us busy
    "aug.device_ms_per_step": 0.0, "encoder.stem_ms_per_step": 0.0,
    "encoder.layer1_ms_per_step": 0.040, "encoder.layer2_ms_per_step": 0.0,
    "encoder.layer3_ms_per_step": 0.0, "encoder.layer4_ms_per_step": 0.0,
    "encoder.bwd_share": 0.0, "loss.device_ms_per_step": 0.025,
    "optimizer.device_ms_per_step": 0.015, "step.unattributed_share": 100 * 10 / 170,
    "driver.dispatch_ms_per_step": 50.0, "driver.dispatch_floor_ms": 0.5,
    "driver.drain_wait_ms_per_step": 15.0,
}


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_reader_gives_none_on_a_rehearsal_and_a_number_on_the_fixture(metric, trace, monkeypatch):
    read = harness.load_reader(metric["name"]).read
    assert set(metric["workloads"]) == {"rn50-cifar.pretrain-b256", "rn18-cifar.pretrain-b1024"}
    assert read(rehearsal_run()) is None
    monkeypatch.setattr(sr, "program_text", lambda: ("ring_update", HLO))
    run = fixture_run(trace)
    assert read(run) == pytest.approx(EXPECTED[metric["name"]])
    if metric["source"] == "device_trace":
        # a program that registers nothing (the parent commit): no number, no raise
        monkeypatch.setattr(sr, "program_text", lambda: None)
        assert read(fixture_run(trace)) is None


def test_the_text_is_read_once_a_run(trace, monkeypatch):
    calls = []
    monkeypatch.setattr(sr, "program_text", lambda: calls.append(1) or ("ring_update", HLO))
    run = fixture_run(trace)
    for metric in NEW:
        harness.load_reader(metric["name"]).read(run)
    assert len(calls) == 1


def test_no_registered_program_reads_as_none():
    from simclr_pytorch_distributed_tpu.utils import profiling

    profiling.clear_step_program()
    assert sr.program_text() is None
