"""``encoder.short_conv_ms_per_step`` on ``test_delta_cell``'s fixture: the
bucket ``short_conv`` holds XLA's fusions under the scope and the Mosaic
calls ``short_conv_fwd`` / ``short_conv_bwd`` alike, by their ``op_name``s;
``BENCHMARK.json`` lists the reader for the fifth cell alone; the reader is
silent where the step has no such scope (a rehearsal, a ResNet's step,
Moonlight's step: the parent commit's side of a traced run holds XLA's
fusions under the scope, so it reads a number there too).
"""

import json
import os

import pytest

import delta_scopes as ds
import flops_delta
import flops_latent
import run as harness
import test_delta_cell as cell

NAME = "encoder.short_conv_ms_per_step"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the convolution on its kernel pair: the Mosaic calls under the scope, the
# backward's in the transposed pass
CALLS = {
    "short_conv_fwd.3": f"{cell.P}/{cell.FWD}/{cell.LIN}/short_conv/short_conv_fwd/pallas_call",
    "short_conv_bwd.1": (f"{cell.P}/{cell.BWD}/{cell.LIN}/checkpoint/short_conv/short_conv_bwd/"
                         "pallas_call")}
CALL_NS = (3.0, 4.0)


def _with_calls(monkeypatch):
    """The fixture's step with the two calls before its last instruction."""
    calls = "".join(
        f'  %{name} = (f32[8,16]{{1,0}}, f32[2,4,16]{{2,1,0}}) custom-call(%fusion.1), '
        f'custom_call_target="tpu_custom_call", metadata={{op_name="{op}"}}\n'
        for name, op in CALLS.items())
    monkeypatch.setattr(cell, "NAMES", cell.NAMES[:-1] + tuple(CALLS) + cell.NAMES[-1:])
    monkeypatch.setattr(cell, "NS", cell.NS[:-1] + CALL_NS + cell.NS[-1:])
    return cell.HLO.replace("  ROOT %fusion.7", calls + "  ROOT %fusion.7")


def test_the_calls_go_to_the_bucket_by_their_op_names(monkeypatch):
    text = _with_calls(monkeypatch)
    assert {name: ds.scope_map(text)[name] for name in CALLS} == {
        "short_conv_fwd.3": ("short_conv", "fwd"), "short_conv_bwd.1": ("short_conv", "bwd")}


def test_reads_fusions_and_mosaic_calls_alike(monkeypatch):
    """XLA's path alone: the fusion under the scope (6 ns a step); with the
    kernel pair's calls beside it, their 3 + 4 ns too; the rule's reader
    does not move, the layer's takes the calls in."""
    read = harness.load_reader(NAME).read
    assert read(cell.fixture_run(cell.HLO, monkeypatch)) == pytest.approx(1e3 * 6e-9)
    run = cell.fixture_run(_with_calls(monkeypatch), monkeypatch)
    assert read(run) == pytest.approx(1e3 * 13e-9)
    layer = harness.load_reader("encoder.linear_attn_ms_per_step").read(run)
    assert layer == pytest.approx(1e3 * 43e-9)
    assert harness.load_reader("encoder.delta_scan_ms_per_step").read(run) == (
        pytest.approx(1e3 * 20e-9))


def test_is_silent_without_its_scope(monkeypatch):
    read = harness.load_reader(NAME).read
    assert read({"records": [], "trace": None, "stretches": None, "flops": flops_delta}) is None
    for text, flops in ((cell.RESNET_HLO, harness.load_module("flops.py")),
                        (cell.RESNET_HLO, flops_delta), (cell.LATENT_HLO, flops_latent),
                        (cell.LATENT_HLO, flops_delta)):
        run = cell.fixture_run(text, monkeypatch)
        run["flops"] = flops
        assert read(run) is None


def test_benchmark_json_lists_the_reader_for_the_fifth_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "ms", "better": "lower", "source": "device_trace",
                     "layer": "encoder", "moves": "pretrain_imgs_per_s",
                     "workloads": [cell.CELL]}
    doc = harness.load_reader(NAME).__doc__
    assert 'layer "encoder"' in doc and "pretrain_imgs_per_s" in doc
    assert NAME in {m["name"] for m in harness.listed_metrics(cell.CELL, trace=True)}
    others = [w["name"] for w in bench["workloads"] if w["name"] != cell.CELL]
    assert all(NAME not in {m["name"] for m in harness.listed_metrics(w, trace=True)}
               for w in others)
