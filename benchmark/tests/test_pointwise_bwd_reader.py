"""``encoder.pointwise_bwd_ms_per_step`` on a step text and a trace made by
hand: one Mosaic call of the tail's backward kernel under
``encoder/layer1_block0`` and one of the loss's; the reader counts the first
only, and reports nothing where the step holds neither."""

import pytest

import run as harness
import trace_reduce as tr

P = "jit(ring_update)"
CALL = 'custom_call_target="tpu_custom_call"'
HLO = f"""
HloModule jit_ring_update

ENTRY %main.1 (a: f32[8,16]) -> f32[8,16] {{
  %a = f32[8,16]{{1,0}} parameter(0)
  %pointwise_bwd.3 = f32[8,16]{{1,0}} custom-call(%a), {CALL}, metadata={{op_name="{P}/transpose(jvp(SupConResNet))/encoder/layer1_block0/layer1_block0._tail_one_backward/pointwise_bwd/pallas_call"}}
  %fusion.1 = f32[8,16]{{1,0}} fusion(%pointwise_bwd.3), kind=kLoop, calls=%f, metadata={{op_name="{P}/transpose(jvp(SupConResNet))/encoder/layer1_block0/Conv_1/mul"}}
  ROOT %_bwd_kernel.2 = f32[8,16]{{1,0}} custom-call(%fusion.1), {CALL}, metadata={{op_name="{P}/transpose(jvp(loss))/pallas_call"}}
}}
"""


def run_of(text, names):
    """Three executions of the step, 100 ns apart, each running ``names`` for
    10, 20 and 5 ns; the stretch holds two of them."""
    ops, modules = [], []
    for step in range(3):
        t = 100.0 * step
        modules.append(["jit_ring_update(1)", t, 90.0, {}])
        for name, (start, ns) in zip(names, ((0.0, 10.0), (10.0, 20.0), (30.0, 5.0))):
            ops.append([f"%{name} = f32[8,16]{{1,0}} custom-call(%a)", t + start, ns, {}])
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": modules}, {"name": tr.OPS_LINE, "events": ops}]}
    return {"planes": [plane], "stretches": [(0.0, 200.0, 2)], "worst": 0,
            "kinds": tr.hlo_kinds(text)}


@pytest.fixture
def read():
    return harness.load_reader("encoder.pointwise_bwd_ms_per_step").read


def test_counts_the_encoders_kernel_and_not_the_losss(read):
    run = run_of(HLO, ("pointwise_bwd.3", "fusion.1", "_bwd_kernel.2"))
    assert run["kinds"] == {"pointwise_bwd.3": "pallas", "_bwd_kernel.2": "pallas"}
    assert read(run) == pytest.approx(1e3 * 10e-9)  # 2 x 10 ns over 2 steps
    # the accepted loss reader takes every Mosaic call: both (PERF.md, section 7)
    assert harness.load_reader("loss.kernel_ms_per_step").read(run) == pytest.approx(1e3 * 15e-9)


@pytest.mark.parametrize("kept", [("_bwd_kernel.2",), ()], ids=["the_loss_s_alone", "no_mosaic_call"])
def test_none_where_the_step_lacks_the_kernel(read, kept):
    """The parent commit, or a BasicBlock encoder: the loss's call alone; and
    a step with no Mosaic call at all."""
    text = "\n".join(ln for ln in HLO.splitlines() if "custom-call" not in ln
                     or any(f"%{name} =" in ln for name in kept))
    run = run_of(text, ("fusion.1", "_bwd_kernel.2"))
    assert set(run["kinds"]) == set(kept)
    assert read(run) is None


def test_none_without_a_trace_or_kinds(read):
    assert read({"records": [], "trace": None, "stretch": None, "kinds": None}) is None
    assert read(dict(run_of(HLO, ("pointwise_bwd.3",)), kinds=None)) is None
