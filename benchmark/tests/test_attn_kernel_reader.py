"""``encoder.attn_kernel_ms_per_step`` on a step text and a trace made by
hand: the forward and the backward Mosaic call of sparse attention's kernel
pair inside an attention layer's loop body, a grouped product of the expert
layers and the loss's call; the reader counts the first two only, reports
nothing where the step holds neither (the parent commit), and
``token_scopes`` puts both under ``attn``."""

import pytest

import run as harness
import token_scopes as ts
import trace_reduce as tr

P = "jit(ring_update)"
CALL = 'custom_call_target="tpu_custom_call"'
ATTN = "encoder/block0/attn"
HLO = f"""
HloModule jit_ring_update

%body.1 (a: f32[8,16]) -> f32[8,16] {{
  %a = f32[8,16]{{1,0}} parameter(0)
  %sparse_attention_fwd.21 = f32[8,16]{{1,0}} custom-call(%a), {CALL}, metadata={{op_name="{P}/jvp(SupConResNet)/{ATTN}/while/body/closed_call/sparse_attention_fwd/pallas_call"}}
  %fusion.7 = f32[8,16]{{1,0}} fusion(%sparse_attention_fwd.21), kind=kLoop, calls=%f, metadata={{op_name="{P}/jvp(SupConResNet)/{ATTN}/while/body/closed_call/indexer/mul"}}
  ROOT %sparse_attention_bwd.13 = f32[8,16]{{1,0}} custom-call(%fusion.7), {CALL}, metadata={{op_name="{P}/transpose(jvp(SupConResNet))/{ATTN}/while/body/closed_call/checkpoint/sparse_attention_bwd/pallas_call"}}
}}

ENTRY %main.1 (a: f32[8,16]) -> f32[8,16] {{
  %a = f32[8,16]{{1,0}} parameter(0)
  %ragged-dot-none.3 = f32[8,16]{{1,0}} custom-call(%a), {CALL}, metadata={{op_name="ragged-dot-none"}}
  ROOT %_bwd_kernel.2 = f32[8,16]{{1,0}} custom-call(%ragged-dot-none.3), {CALL}, metadata={{op_name="{P}/transpose(jvp(loss))/pallas_call"}}
}}
"""
NAMES = ("sparse_attention_fwd.21", "fusion.7", "sparse_attention_bwd.13", "ragged-dot-none.3",
         "_bwd_kernel.2")
NS = (10.0, 20.0, 30.0, 5.0, 2.0)


def run_of(text, names=NAMES):
    """Three executions of the step, 100 ns apart, each running ``names`` one
    after the other for ``NS`` ns; the stretch holds two of them."""
    ops, modules = [], []
    for step in range(3):
        t = 100.0 * step
        modules.append(["jit_ring_update(1)", t, 90.0, {}])
        for name, ns in zip(NAMES, NS):
            if name in names:
                ops.append([f"%{name} = f32[8,16]{{1,0}} custom-call(%a)", t, ns, {}])
            t += ns
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": modules}, {"name": tr.OPS_LINE, "events": ops}]}
    return {"planes": [plane], "stretches": [(0.0, 200.0, 2)], "worst": 0,
            "kinds": tr.hlo_kinds(text)}


@pytest.fixture
def read():
    return harness.load_reader("encoder.attn_kernel_ms_per_step").read


def test_counts_the_pair_and_neither_the_experts_nor_the_losss(read):
    run = run_of(HLO)
    assert {n for n, kind in run["kinds"].items() if kind == "pallas"} == set(NAMES) - {"fusion.7"}
    assert read(run) == pytest.approx(1e3 * 40e-9)  # 2 x (10 + 30) ns over 2 steps


def test_token_scopes_puts_the_calls_under_attn():
    """They sit in a loop body under the layer's module path, so
    ``encoder.attn_ms_per_step`` still holds the whole layer."""
    scopes = ts.scope_map(HLO)
    assert scopes["sparse_attention_fwd.21"] == ("attn", "fwd")
    assert scopes["sparse_attention_bwd.13"] == ("attn", "bwd")
    assert scopes["fusion.7"] == ("indexer", "fwd")
    assert scopes["ragged-dot-none.3"][0] == "experts"


@pytest.mark.parametrize("kept", [("ragged-dot-none.3", "_bwd_kernel.2"), ()],
                         ids=["xlas_path", "no_mosaic_call"])
def test_none_where_the_step_lacks_the_kernels(read, kept):
    """The parent commit's step: the expert layers' and the loss's calls
    alone; and a step with no Mosaic call at all."""
    text = "\n".join(ln for ln in HLO.splitlines() if "custom-call" not in ln
                     or any(f"%{name} =" in ln for name in kept))
    run = run_of(text, names=("fusion.7",) + tuple(kept))
    assert set(run["kinds"]) == set(kept)
    assert read(run) is None


def test_none_without_a_trace_or_kinds(read):
    assert read({"records": [], "trace": None, "stretch": None, "kinds": None}) is None
    assert read(dict(run_of(HLO), kinds=None)) is None
