"""The reduction from a trace to numbers, on a timeline made by hand
(``timeline.json``: two chips, four executions of the step program each)."""

import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))

# the compiled step's text, cut down to what the timeline's operations need
HLO = """
HloModule jit_ring_update

%fused_computation.1 (p0: f32[8,4,4,16], p1: f32[3,3,16,16]) -> f32[8,4,4,16] {
  %p0 = f32[8,4,4,16]{3,2,1,0} parameter(0)
  %p1 = f32[3,3,16,16]{3,2,1,0} parameter(1)
  %convolution.9 = f32[8,4,4,16]{3,2,1,0} convolution(f32[8,4,4,16]{3,2,1,0} %p0, f32[3,3,16,16]{3,2,1,0} %p1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
  ROOT %maximum.1 = f32[8,4,4,16]{3,2,1,0} maximum(f32[8,4,4,16]{3,2,1,0} %convolution.9, f32[8,4,4,16]{3,2,1,0} %p0)
}

%fused_computation.7 (p0: f32[8,16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  ROOT %multiply.4 = f32[8,16]{1,0} multiply(f32[8,16]{1,0} %p0, f32[8,16]{1,0} %p0)
}

ENTRY %main.1 (a: f32[8,4,4,16], w: f32[3,3,16,16], z: f32[8,16]) -> f32[8,16] {
  %a = f32[8,4,4,16]{3,2,1,0} parameter(0)
  %w = f32[3,3,16,16]{3,2,1,0} parameter(1)
  %z = f32[8,16]{1,0} parameter(2)
  %fusion.1 = f32[8,4,4,16]{3,2,1,0} fusion(f32[8,4,4,16]{3,2,1,0} %a, f32[3,3,16,16]{3,2,1,0} %w), kind=kOutput, calls=%fused_computation.1
  %all-reduce.3 = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %z), replica_groups={{0,1}}, to_apply=%add
  %fusion.7 = f32[8,16]{1,0} fusion(f32[8,16]{1,0} %all-reduce.3), kind=kLoop, calls=%fused_computation.7
  ROOT %custom-call.2 = f32[8,16]{1,0} custom-call(f32[8,16]{1,0} %fusion.7), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[8,16]{1,0}}
}
"""


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "timeline.json")) as f:
        return json.load(f)


def test_planes_and_stretch(trace):
    planes = tr.device_planes(trace)
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/device:TPU:1"]
    # the first execution is left out; the stretch runs from the second's start
    # to the last one's start and holds two whole steps
    assert tr.steady_stretch(planes[0], "ring_update") == (100000.0, 300000.0, 2)
    assert tr.steady_stretch(planes[0], "no_such_program") is None


def test_idle_share_and_gaps(trace):
    p0, p1 = tr.device_planes(trace)
    # chip 0, [100, 300] us: busy 100-185 (85) and 200-285 (85) = 170 of 200 us
    assert tr.busy_seconds(p0, 100000.0, 300000.0) == pytest.approx(170e-6)
    gaps = tr.idle_gaps(p0, 100000.0, 300000.0)
    assert [(s, round(d * 1e6, 3)) for s, d in gaps] == [(185000.0, 15.0), (285000.0, 15.0)]
    # chip 1 is busy 100 of 200 us
    assert tr.busy_seconds(p1, 100000.0, 300000.0) == pytest.approx(100e-6)


def test_conv_loss_and_collective_time(trace):
    p0 = tr.device_planes(trace)[0]
    t0, t1, steps = tr.steady_stretch(p0, "ring_update")
    kinds = tr.hlo_kinds(HLO)
    assert kinds == {"fusion.1": "conv", "custom-call.2": "pallas", "all-reduce.3": "collective",
                     "convolution.9": "conv"}
    assert tr.seconds_where(p0, t0, t1, tr.is_kind("conv", kinds)) / steps == pytest.approx(40e-6)
    assert tr.seconds_where(p0, t0, t1, tr.is_kind("pallas", kinds)) / steps == pytest.approx(5e-6)
    # step 2's all-reduce (140-160) overlaps a fusion from 150: 10 us exposed;
    # step 3's (240-260) overlaps nothing: 20 us exposed
    assert tr.exposed_collective_seconds(p0, t0, t1, kinds) == pytest.approx(30e-6)
    assert tr.seconds_by_kind(p0, t0, t1, kinds) == {
        "conv": pytest.approx(80e-6), "collective": pytest.approx(40e-6),
        "other": pytest.approx(50e-6), "pallas": pytest.approx(10e-6)}


def test_top_ops_and_anchor(trace):
    p0 = tr.device_planes(trace)[0]
    top = tr.top_ops(p0, 100000.0, 300000.0, tr.hlo_kinds(HLO))
    assert top[0] == ["fusion.1[conv]", pytest.approx(80e-6)]
    assert ["all-reduce.3[collective]", pytest.approx(40e-6)] in top
    assert ["fusion.7", pytest.approx(50e-6)] in top
    assert tr.find_host_event(trace, "bench_trace_anchor") == 500.0
    assert tr.find_host_event(trace, "absent") is None


def test_interval_algebra():
    assert tr.merge([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.total(tr.clip([(0, 10), (20, 30)], 5, 25)) == 10
