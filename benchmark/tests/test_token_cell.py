"""The cell ``keye-vl2-a3b-ep8.pretrain-1024px-b4``: its rehearsal through
``run.drive`` with the tiny preset (the harness shrinks image and batch, never
the model, so the test names the preset that program and reference both
know); the faults and the control read ``correct`` false; ``flops_tokens``
against a count by hand; each new reader silent on a rehearsal and on a
ResNet's step, and a number on a fixture.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import flops_tokens
import run as harness
import token_scopes as ts
import trace_reduce as tr

CELL = "keye-vl2-a3b-ep8.pretrain-1024px-b4"
TINY = ["--model", "keye-vl2-tiny"]
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["encoder.attn_ms_per_step", "encoder.indexer_ms_per_step", "encoder.moe_ms_per_step",
       "encoder.expert_matmul_roofline_share", "moe.load_max_over_mean", "moe.held_share"]


def rehearse(seed, overrides=(), **kw):
    return harness.drive(CELL, seed, 1.0, False, rehearse=True,
                         flag_overrides=TINY + list(overrides), **kw)


def over_a_limit(res):
    return [k for k, row in res["compared"].items() if not row["value"] <= row["limit"]]


def test_rehearsal_walks_the_cell():
    res = rehearse(2147483652)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] == {} and list(res)[-1] == "compared"
    assert set(res["compared"]) == set(harness.load_cell(CELL)["limits"]) | {"compiled_in_window"}
    assert res["compared"]["compiled_in_window"] == {"value": 0, "limit": 0}
    # the CPU's products are exact: the two sides differ by rounding order alone
    assert res["compared"]["stats_early_diff"]["value"] < 1e-6
    assert res["compared"]["grad_median_gap"]["value"] < 1e-5


def test_fault_state_left_unchanged(monkeypatch):
    from simclr_pytorch_distributed_tpu.train import supcon

    real_make = supcon.make_fused_update

    def broken(*a, **k):
        real = real_make(*a, **k)

        def update(state, ring, images, labels, key):
            before = jax.tree.map(jnp.copy, state)
            after, ring = real(state, ring, images, labels, key)
            return before.replace(step=after.step), ring
        return update

    monkeypatch.setattr(supcon, "make_fused_update", broken)
    res = rehearse(21)
    assert res["correct"] is False
    assert res["compared"]["change_median_gap"]["value"] > 0.9  # reads about 1


def test_control_lower_precision_is_not_correct():
    res = rehearse(22, ["--bf16"])
    assert res["correct"] is False and over_a_limit(res)


def test_flops_against_a_count_by_hand():
    """One layer of the published widths over one row of 4,096 tokens."""
    a = flops_tokens.reference.arch("keye-vl2-a3b-ep8")
    t, d = 4096, 2048
    per = flops_tokens.layer_macs_per_row(a, t)
    assert per["projections"] == (t * d * (4096 + 512 + 512) + t * 4096 * d, 3)
    attended = 2048 * 2049 // 2 + 2048 * 2048  # t + 1 keys up to 2048, then 2048 each
    assert per["attention"] == (2 * attended * 32 * 128, 3)
    assert per["indexer_projections"] == (t * d * (1024 + 64 + 16), 2)
    assert per["indexer_scores_forward"] == (t * (t + 1) // 2 * 16 * 65, 1)
    assert per["indexer_scores_backward"] == (attended * 16 * 65, 2)
    assert per["router"] == (t * d * 128, 3)
    assert per["experts"] == (t * 1.0 * 3 * d * 768, 3)  # 8 of 128, 16 held: one a token
    layers = a["num_hidden_layers"]
    rows = 8
    by_hand = 2 * rows * (2 * t * 768 * d + layers * sum(m * p for m, p in per.values())
                          + 3 * (d * d + d * 128)) + 3 * 2 * rows * rows * 128
    assert flops_tokens.step_flops("keye-vl2-a3b-ep8", 1024, 4) == pytest.approx(by_hand)
    assert flops_tokens.flops_per_image("keye-vl2-a3b-ep8", 1024, 4) == pytest.approx(by_hand / 4)
    assert flops_tokens.expert_matmul_flops_per_step("keye-vl2-a3b-ep8", 1024, rows) == (
        pytest.approx(2 * 3 * rows * t * 3 * d * 768 * layers))
    m = rows * t
    assert flops_tokens.expert_matmul_min_bytes_per_step("keye-vl2-a3b-ep8", 1024, rows) == (
        pytest.approx(9 * (m * d + 16 * d * 768 + m * 768) * 4 * layers))
    least, side = flops_tokens.expert_matmul_min_seconds("keye-vl2-a3b-ep8", 1024, rows,
                                                         197e12, 819e9)
    assert side == "bytes" and least == pytest.approx(9 * (m * d + 16 * d * 768 + m * 768)
                                                      * 4 * layers / 819e9)


def test_benchmark_json_lists_the_cell_and_its_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.load_cell(CELL)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
        entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert len(entry["why"]) <= 200
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["config_file"]["reduced"] == config["reduced"]
    assert cell["config_file"]["source"].startswith(config["source"])
    for key in config["reduced"]:  # the published count stands beside the held one
        assert cell["config_file"]["published"][key] != cell["config_file"][key]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL] and listed[name]["moves"] == "pretrain_imgs_per_s"
        doc = harness.load_reader(name).__doc__
        assert f'layer "{listed[name]["layer"]}"' in doc and "pretrain_imgs_per_s" in doc
    reports = {m["name"] for m in harness.listed_metrics(CELL, trace=True)}
    assert set(NEW) | {"step.mfu.pretrain", "device.peak_mem_share"} <= reports
    assert not {"loss.kernel_ms_per_step", "encoder.conv_ms_per_step"} & reports


# ------------------------------------------------ the readers on a fixture

P = "jit(ring_update)"
FWD, BWD = "jvp(SupConResNet)", "transpose(jvp(SupConResNet))"
HLO = f"""
HloModule jit_ring_update

ENTRY %main.1 (a: f32[8,16]) -> f32[8,16] {{
  %a = f32[8,16]{{1,0}} parameter(0)
  %fusion.1 = f32[8,16]{{1,0}} fusion(%a), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/encoder/block0/attn/dot_general"}}
  %fusion.2 = f32[8,16]{{1,0}} fusion(%fusion.1), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/encoder/block0/attn/while/body/checkpoint/indexer/reduce_sum"}}
  %fusion.3 = f32[8,16]{{1,0}} fusion(%fusion.2), kind=kLoop, calls=%f, metadata={{op_name="{P}/{BWD}/encoder/block1/moe/sort"}}
  %fusion.4 = f32[8,16]{{1,0}} fusion(%fusion.3), kind=kLoop, calls=%f, metadata={{op_name="{P}/{BWD}/encoder/block1/moe/while/body/experts/mul"}}
  %ragged-dot-none.4 = f32[8,16]{{1,0}} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  ROOT %fusion.5 = f32[8,16]{{1,0}} fusion(%ragged-dot-none.4), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/encoder/patch_embed/dot_general"}}
}}
"""
NAMES = ("fusion.1", "fusion.2", "fusion.3", "fusion.4", "ragged-dot-none.4", "fusion.5")
NS = (10.0, 20.0, 5.0, 4.0, 36.0, 2.0)


def fixture_run(text, monkeypatch):
    """Three executions of the step, 100 ns apart; the stretch holds two."""
    ops, modules, t = [], [], 0.0
    for step in range(3):
        t = 100.0 * step
        modules.append(["jit_ring_update(1)", t, 90.0, {}])
        for name, ns in zip(NAMES, NS):
            ops.append([f"%{name} = f32[8,16]{{1,0}} fusion(%a)", t, ns, {}])
            t += ns
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": modules}, {"name": tr.OPS_LINE, "events": ops}]}
    monkeypatch.setattr(ts.sr, "program_text", lambda: ("ring_update", text))
    records = [{"name": "bench_window_start", "track": "bench", "ts": 1.0},
               {"name": "health_window", "track": "health", "ts": 2.0,
                "args": {"moe_held_share": 0.11, "moe_load_max_over_mean": 1.7, "step": 10}},
               {"name": "health_window", "track": "health", "ts": 3.0,
                "args": {"moe_held_share": 0.125, "moe_load_max_over_mean": 1.25, "step": 20}},
               {"name": "bench_window_end", "track": "bench", "ts": 4.0}]
    return {"planes": [plane], "stretches": [(0.0, 200.0, 2)], "worst": 0, "records": records,
            "flops": flops_tokens, "config": {"model": "keye-vl2-a3b-ep8"}, "size": 1024,
            "global_batch": 4, "chips": 1,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_buckets_by_the_innermost_scope():
    scopes = ts.scope_map(HLO)
    assert scopes == {"fusion.1": ("attn", "fwd"), "fusion.2": ("indexer", "fwd"),
                      "fusion.3": ("moe", "bwd"), "fusion.4": ("experts", "bwd"),
                      # the compiler's own kernel: by its name, direction by its neighbours
                      "ragged-dot-none.4": ("experts", "fwd"), "fusion.5": ("embed", "fwd")}
    assert ts.bucket_of(f"{P}/{BWD}/loss/mul") == ("loss", "bwd")
    assert ts.bucket_of(f"{P}/{FWD}/proj_head/fc1/dot_general") == ("head", "fwd")
    assert ts.bucket_of("state.params") is None


def test_readers_on_the_fixture(monkeypatch):
    run = fixture_run(HLO, monkeypatch)
    read = lambda name: harness.load_reader(name).read(run)  # noqa: E731
    assert read("encoder.attn_ms_per_step") == pytest.approx(1e3 * 10e-9)
    assert read("encoder.indexer_ms_per_step") == pytest.approx(1e3 * 20e-9)
    assert read("encoder.moe_ms_per_step") == pytest.approx(1e3 * 45e-9)
    least, _ = flops_tokens.expert_matmul_min_seconds("keye-vl2-a3b-ep8", 1024, 8, 197e12, 819e9)
    assert read("encoder.expert_matmul_roofline_share") == pytest.approx(100 * least / 40e-9)
    assert read("moe.load_max_over_mean") == 1.25 and read("moe.held_share") == 12.5
    got = ts.scope_seconds(run)
    assert "moe" in ts.table(got["by_scope"], got["busy_s"], got["steps"])
    assert sum(got["by_scope"].values()) == pytest.approx(got["busy_s"])


RESNET_HLO = HLO.replace("block0/attn", "layer1_block0/Conv_0").replace(
    "block1/moe", "layer2_block0/Conv_1")


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_without_its_scopes(name, monkeypatch):
    """A rehearsal (no trace, no health window), and a ResNet's step under
    the same readers (the parent commit's side of a traced run)."""
    read = harness.load_reader(name).read
    assert read({"records": [], "trace": None, "stretches": None, "flops": flops_tokens}) is None
    run = fixture_run(RESNET_HLO, monkeypatch)
    run["records"] = [r for r in run["records"] if r["name"] != "health_window"]
    run["flops"] = harness.load_module("flops.py")
    assert read(run) is None
