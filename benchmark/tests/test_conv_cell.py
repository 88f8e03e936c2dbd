"""The cell ``lfm2-8b-a1b-ep4.pretrain-1024px-b4``: its rehearsal through
``run.drive`` with the tiny preset (the harness shrinks image and batch,
never the model, so the test names the preset that program and reference
both know), and under the control; a state left unchanged reads ``correct``
false; ``flops_conv`` against a count by hand; ``BENCHMARK.json`` lists the
cell, its files and its readers; each new reader a number on a fixture and
silent on a rehearsal, on a ResNet's step and on the other token steps.
"""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import pytest

import conv_scopes as cs
import flops_conv
import flops_delta
import flops_latent
import flops_tokens
import run as harness
import trace_reduce as tr

CELL = "lfm2-8b-a1b-ep4.pretrain-1024px-b4"
REAL = "lfm2-8b-a1b-ep4"
TINY = ["--model", "lfm2-tiny"]
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["encoder.conv_mixer_ms_per_step", "encoder.gated_conv_ms_per_step",
       "encoder.gated_conv_roofline_share"]
SHARED = ["step.mfu.pretrain", "device.idle_share.pretrain", "device.peak_mem_share",
          "driver.host_phase_ms_per_step", "driver.dispatch_ms_per_step",
          "driver.dispatch_floor_ms", "driver.drain_wait_ms_per_step", "aug.device_ms_per_step",
          "loss.device_ms_per_step", "optimizer.device_ms_per_step", "step.unattributed_share",
          "encoder.attn_ms_per_step", "encoder.moe_ms_per_step",
          "encoder.expert_matmul_roofline_share", "moe.load_max_over_mean", "moe.held_share",
          "moe.route_bias_max_abs", "setup.boot_s", "setup.import_s", "setup.store_s",
          "setup.trace_lower_s", "setup.compile_s", "setup.first_window_s",
          "setup.unattributed_share"]


def rehearse(seed, overrides=(), **kw):
    return harness.drive(CELL, seed, 1.0, False, rehearse=True,
                         flag_overrides=TINY + list(overrides), **kw)


def over_a_limit(res):
    return [k for k, row in res["compared"].items() if not row["value"] <= row["limit"]]


def test_rehearsal_walks_the_cell():
    res = rehearse(2147483653)
    assert res["correct"] is True and res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] == {} and list(res)[-1] == "compared"
    assert set(res["compared"]) == set(harness.load_cell(CELL)["limits"]) | {"compiled_in_window"}
    assert res["compared"]["compiled_in_window"] == {"value": 0, "limit": 0}
    # the CPU's products are exact: the two sides differ by rounding order alone
    assert res["compared"]["stats_median_diff"]["value"] < 1e-6
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


def test_control_lower_precision_shows_in_the_number_that_catches_it_on_the_chip():
    """``stats_median_diff`` reads the four expert layers' ``prob_mean``
    (``reference_conv.stats_order``). At the tiny size on the CPU's exact
    products the control reads about 1.4e-5 where a sound rehearsal reads
    under 1e-6; the cell's limit, 5e-5, is set from the chip's readings at
    the real size (sound seeds 7.2e-6 to 1.33e-5, ``--bf16`` 1.76-1.97e-4:
    PERF.md, "How ``correct`` is decided"), so here the control stands far
    over the sound side and under that limit."""
    res = rehearse(22, ["--bf16"])
    assert res["compared"]["stats_median_diff"]["value"] > 5e-6
    assert not over_a_limit(res)


def test_flops_against_a_count_by_hand():
    """The published widths over rows of 4,096 tokens."""
    a = flops_conv.reference.arch(REAL)
    t, d = 4096, 2048
    mixer = flops_conv.mixer_macs_per_row(a, t)
    assert mixer["conv_projections"] == t * d * (3 * d + d)
    assert mixer["gated_conv"] == t * d * (3 + 2)
    assert mixer["attn_projections"] == t * d * (2048 + 512 + 512 + 2048)
    assert mixer["attn_core"] == t * (t + 1) // 2 * 32 * 128
    ff = flops_conv.feed_forward_macs_per_row(a, t)
    assert ff == {"dense": t * 3 * d * 7168, "router": t * d * 32,
                  "experts": t * 4 * 8 / 32 * 3 * d * 1792}  # 4 of 32, 8 held: one a token
    rows = 8
    per_row = (2 * t * 768 * d + 3 * 4 * (mixer["conv_projections"] + mixer["gated_conv"])
               + 3 * (mixer["attn_projections"] + mixer["attn_core"]) + 3 * ff["dense"]
               + 3 * 4 * (ff["router"] + ff["experts"]) + 3 * (d * d + d * 128))
    by_hand = 2 * rows * per_row + 3 * 2 * rows * rows * 128
    assert flops_conv.step_flops(REAL, 1024, 4) == pytest.approx(by_hand)
    assert flops_conv.flops_per_image(REAL, 1024, 4) == pytest.approx(by_hand / 4)
    # the shares of the layers' counted work: the conv mixers 38.5%, the
    # dense layer 25.3%, the held experts with their routers 25.4%, attention
    # 10.8% (at 4,096 causal keys)
    conv = 4 * (mixer["conv_projections"] + mixer["gated_conv"])
    attn = mixer["attn_projections"] + mixer["attn_core"]
    moe = 4 * (ff["router"] + ff["experts"])
    layers = conv + attn + ff["dense"] + moe
    assert [round(100 * x / layers, 1) for x in (conv, ff["dense"], moe, attn)] == [
        38.5, 25.3, 25.4, 10.8]
    m = rows * t * 4 * 8 / 32
    assert flops_conv.expert_matmul_flops_per_step(REAL, 1024, rows) == (
        pytest.approx(2 * 3 * m * 3 * d * 1792 * 4))
    assert flops_conv.expert_matmul_min_bytes_per_step(REAL, 1024, rows) == (
        pytest.approx(9 * (m * d + 8 * d * 1792 + m * 1792) * 4 * 4))
    assert (inspect.signature(flops_conv.expert_matmul_min_seconds)
            == inspect.signature(flops_tokens.expert_matmul_min_seconds))
    # the gated convolution's core: five multiply-adds a channel, three
    # passes; B, C, x and y, then B, C, x, dy, dB, dC, dx a row, the taps
    # once a layer
    assert flops_conv.gated_conv_flops_per_step(REAL, 1024, rows) == pytest.approx(
        2 * 3 * t * d * 5 * rows * 4)
    assert flops_conv.gated_conv_min_bytes_per_step(REAL, 1024, rows) == pytest.approx(
        (11 * t * d * rows + 3 * 3 * d) * 4 * 4)
    least, side = flops_conv.gated_conv_min_seconds(REAL, 1024, rows, 197e12, 819e9)
    assert side == "bytes" and least == pytest.approx(
        flops_conv.gated_conv_min_bytes_per_step(REAL, 1024, rows) / 819e9)
    assert 0.0144 < least < 0.0145  # 11.8 GB a step at 819 GB/s


def test_benchmark_json_lists_the_cell_its_files_and_its_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.load_cell(CELL)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
        entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert len(entry["why"]) <= 200 and entry["chips"] == 1
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert len(config["why"]) <= 200
    assert config["file"] == f"benchmark/configs/{REAL}.json"
    assert cell["config_file"]["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert cell["config_file"]["source"].startswith(config["source"])
    for key in config["reduced"]:  # the published count stands beside the held one
        assert cell["config_file"]["published"][key] != cell["config_file"][key]
    for key in ("reference", "adapter", "flops"):  # the files the configuration names are there
        assert os.path.exists(os.path.join(ROOT, "benchmark", cell["config_file"][key]))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL] and listed[name]["moves"] == "pretrain_imgs_per_s"
        doc = harness.load_reader(name).__doc__
        assert f'layer "{listed[name]["layer"]}"' in doc and "pretrain_imgs_per_s" in doc
    for name in SHARED:
        assert CELL in listed[name]["workloads"]
    reports = {m["name"] for m in harness.listed_metrics(CELL, trace=True)}
    assert reports == set(NEW) | set(SHARED)


# ------------------------------------------------ the readers on a fixture

P = "jit(ring_update)"
FWD, BWD = "jvp(SupConResNet)", "transpose(jvp(SupConResNet))"
MIX = "encoder/block0/attn/conv_mixer/while/body/checkpoint"
HLO = f"""
HloModule jit_ring_update

ENTRY %main.1 (a: f32[8,16]) -> f32[8,16] {{
  %a = f32[8,16]{{1,0}} parameter(0)
  %fusion.1 = f32[8,16]{{1,0}} fusion(%a), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/{MIX}/dot_general"}}
  %fusion.2 = f32[8,16]{{1,0}} fusion(%fusion.1), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/{MIX}/gated_conv/mul"}}
  %fusion.3 = f32[8,16]{{1,0}} fusion(%fusion.2), kind=kLoop, calls=%f, metadata={{op_name="{P}/{BWD}/{MIX}/gated_conv/add"}}
  %fusion.4 = f32[8,16]{{1,0}} fusion(%fusion.3), kind=kLoop, calls=%f, metadata={{op_name="{P}/{BWD}/encoder/block1/attn/while/body/checkpoint/attn_core/exp"}}
  %fusion.5 = f32[8,16]{{1,0}} fusion(%fusion.4), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/encoder/block1/moe/sort"}}
  %fusion.6 = f32[8,16]{{1,0}} fusion(%fusion.5), kind=kLoop, calls=%f, metadata={{op_name="{P}/{BWD}/encoder/block0/mlp/while/body/dot_general"}}
  %ragged-dot-none.4 = f32[8,16]{{1,0}} custom-call(%fusion.6), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  ROOT %fusion.7 = f32[8,16]{{1,0}} fusion(%ragged-dot-none.4), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/encoder/patch_embed/dot_general"}}
}}
"""
NAMES = ("fusion.1", "fusion.2", "fusion.3", "fusion.4", "fusion.5", "fusion.6",
         "ragged-dot-none.4", "fusion.7")
NS = (10.0, 6.0, 20.0, 8.0, 5.0, 7.0, 30.0, 2.0)


def fixture_run(text, monkeypatch, flops=flops_conv):
    """Three executions of the step, 100 ns apart; the stretch holds two."""
    ops, modules = [], []
    for step in range(3):
        t = 100.0 * step
        modules.append(["jit_ring_update(1)", t, 90.0, {}])
        for name, ns in zip(NAMES, NS):
            ops.append([f"%{name} = f32[8,16]{{1,0}} fusion(%a)", t, ns, {}])
            t += ns
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": tr.MODULES_LINE, "events": modules}, {"name": tr.OPS_LINE, "events": ops}]}
    monkeypatch.setattr(cs.sr, "program_text", lambda: ("ring_update", text))
    records = [{"name": "bench_window_start", "track": "bench", "ts": 1.0},
               {"name": "health_window", "track": "health", "ts": 2.0,
                "args": {"moe_held_share": 0.27, "route_bias_max_abs": 0.004, "step": 10}},
               {"name": "health_window", "track": "health", "ts": 3.0,
                "args": {"moe_held_share": 0.25, "moe_load_max_over_mean": 1.5,
                         "route_bias_max_abs": 0.006, "step": 20}},
               {"name": "bench_window_end", "track": "bench", "ts": 4.0}]
    return {"planes": [plane], "stretches": [(0.0, 200.0, 2)], "worst": 0, "records": records,
            "flops": flops, "config": {"model": REAL}, "size": 1024,
            "global_batch": 4, "chips": 1,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_buckets_by_the_innermost_scope():
    assert cs.scope_map(HLO) == {
        "fusion.1": ("conv_mixer", "fwd"), "fusion.2": ("gated_conv", "fwd"),
        "fusion.3": ("gated_conv", "bwd"), "fusion.4": ("attn", "bwd"),
        "fusion.5": ("moe", "fwd"), "fusion.6": ("embed", "bwd"),
        # the compiler's own kernel: by its name, direction by its neighbours
        "ragged-dot-none.4": ("experts", "fwd"), "fusion.7": ("embed", "fwd")}
    assert cs.bucket_of(f"{P}/{BWD}/loss/mul") == ("loss", "bwd")
    assert cs.bucket_of("state.params") is None
    assert cs.is_conv_step(HLO)
    import token_scopes as ts  # the accepted machinery is not changed by the rebinding
    assert ts.bucket_of(f"{P}/{FWD}/{MIX}/gated_conv/mul") == ("attn", "fwd")


def test_readers_on_the_fixture(monkeypatch):
    run = fixture_run(HLO, monkeypatch)
    read = lambda name: harness.load_reader(name).read(run)  # noqa: E731
    assert read("encoder.conv_mixer_ms_per_step") == pytest.approx(1e3 * 36e-9)
    assert read("encoder.gated_conv_ms_per_step") == pytest.approx(1e3 * 26e-9)
    least, _ = flops_conv.gated_conv_min_seconds(REAL, 1024, 8, 197e12, 819e9)
    assert read("encoder.gated_conv_roofline_share") == pytest.approx(100 * least / 26e-9)
    # the accepted token readers, under the names they have: every mixer is
    # attention, the dense layer sits with the embedding
    assert read("encoder.attn_ms_per_step") == pytest.approx(1e3 * 44e-9)
    assert read("encoder.moe_ms_per_step") == pytest.approx(1e3 * 35e-9)
    least, _ = flops_conv.expert_matmul_min_seconds(REAL, 1024, 8, 197e12, 819e9)
    assert read("encoder.expert_matmul_roofline_share") == pytest.approx(100 * least / 30e-9)
    assert read("moe.load_max_over_mean") == 1.5 and read("moe.held_share") == 25.0
    assert read("moe.route_bias_max_abs") == 0.006
    got = cs.scope_seconds(run)
    assert "gated_conv" in cs.table(got["by_scope"], got["busy_s"], got["steps"])
    assert sum(got["by_scope"].values()) == pytest.approx(got["busy_s"])
    # neither the latent nor the linear-attention readers read this cell
    for name in ("encoder.attn_core_ms_per_step", "encoder.latent_ms_per_step",
                 "encoder.delta_scan_ms_per_step", "encoder.short_conv_ms_per_step"):
        assert harness.load_reader(name).read(run) is None


DELTA_HLO = HLO.replace("/conv_mixer/", "/linear_attn/").replace("/gated_conv/", "/short_conv/")
LATENT_HLO = HLO.replace("/conv_mixer/", "/").replace("/gated_conv/", "/latent/")
RESNET_HLO = HLO.replace("block0/attn", "layer1_block0/Conv_0").replace(
    "block1/attn", "layer1_block1/Conv_0").replace("block1/moe", "layer2_block0/Conv_1")


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_without_its_scopes(name, monkeypatch):
    """A rehearsal (no trace, no health window), a ResNet's step, Moonlight's
    and Qwen3-Next's steps under the same readers (the parent commit's side
    of a traced run)."""
    read = harness.load_reader(name).read
    assert read({"records": [], "trace": None, "stretches": None, "flops": flops_conv}) is None
    for text, flops in ((RESNET_HLO, harness.load_module("flops.py")), (RESNET_HLO, flops_conv),
                        (LATENT_HLO, flops_latent), (LATENT_HLO, flops_conv),
                        (DELTA_HLO, flops_delta), (DELTA_HLO, flops_conv)):
        run = fixture_run(text, monkeypatch, flops)
        run["records"] = [r for r in run["records"] if r["name"] != "health_window"]
        assert read(run) is None
