"""The cell ``qwen3-next-80b-a3b-ep32.pretrain-1024px-b4``: its rehearsal
through ``run.drive`` with the tiny preset (the harness shrinks image and
batch, never the model, so the test names the preset that program and
reference both know), and under the control; a state left unchanged reads
``correct`` false; ``flops_delta`` against a count by hand; ``BENCHMARK.json``
lists the cell, its files and its readers; each new reader a number on a
fixture and silent on a rehearsal, on a ResNet's step and on the other token
steps.
"""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import pytest

import delta_scopes as ds
import flops_delta
import flops_latent
import flops_tokens
import run as harness
import trace_reduce as tr

CELL = "qwen3-next-80b-a3b-ep32.pretrain-1024px-b4"
REAL = "qwen3-next-80b-a3b-ep32"
TINY = ["--model", "qwen3-next-tiny"]
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["encoder.linear_attn_ms_per_step", "encoder.delta_scan_ms_per_step",
       "encoder.delta_scan_roofline_share", "delta.decay_mean"]
SHARED = ["step.mfu.pretrain", "device.idle_share.pretrain", "device.peak_mem_share",
          "driver.host_phase_ms_per_step", "driver.dispatch_ms_per_step",
          "driver.dispatch_floor_ms", "driver.drain_wait_ms_per_step", "aug.device_ms_per_step",
          "loss.device_ms_per_step", "optimizer.device_ms_per_step", "step.unattributed_share",
          "encoder.attn_ms_per_step", "encoder.moe_ms_per_step",
          "encoder.expert_matmul_roofline_share", "moe.load_max_over_mean", "moe.held_share"]


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
    """``stats_median_diff`` reads the four layers' ``prob_mean``
    (``reference_delta.stats_order``). At the tiny size on the CPU's exact
    products the control reads about 3.5e-5 where a sound rehearsal reads
    under 1e-6; the cell's limit, 2e-4, is set from the chip's readings at the
    real size (sound seeds 5.1-6.5e-5, ``--bf16`` 7.2-8.4e-4: PERF.md, "How
    ``correct`` is decided"), so here the control stands far over the sound
    side and under that limit."""
    res = rehearse(22, ["--bf16"])
    assert res["compared"]["stats_median_diff"]["value"] > 1e-5
    assert not over_a_limit(res)


def test_flops_against_a_count_by_hand():
    """The published widths over rows of 4,096 tokens."""
    a = flops_delta.reference.arch(REAL)
    t, d, c = 4096, 2048, 64
    mixer = flops_delta.mixer_macs_per_row(a, t)
    assert mixer["linear_projections"] == t * d * (12288 + 64 + 4096)
    assert mixer["full_projections"] == t * d * (8192 + 512 + 512 + 4096)
    assert mixer["attn_core"] == t * (t + 1) // 2 * 16 * 512
    per_chunk = (2016 * 128 + 2080 * 128 + 64 * 63 * 62 // 6 + 2080 * 256 + 3 * 64 * 128 * 128
                 + 2080 * 128)
    assert mixer["delta_scan"] == t // c * 32 * per_chunk
    ff = flops_delta.expert_layer_macs_per_row(a, t)
    assert ff == {"router": t * d * 512, "shared": t * d * (3 * 512 + 1),
                  "experts": t * 10 * 16 / 512 * 3 * d * 512}  # 10 of 512, 16 held
    rows = 8
    per_row = (2 * t * 768 * d + 3 * 3 * (mixer["linear_projections"] + mixer["delta_scan"])
               + 3 * (mixer["full_projections"] + mixer["attn_core"]) + 3 * 4 * sum(ff.values())
               + 3 * (d * d + d * 128))
    by_hand = 2 * rows * per_row + 3 * 2 * rows * rows * 128
    assert flops_delta.step_flops(REAL, 1024, 4) == pytest.approx(by_hand)
    assert flops_delta.flops_per_image(REAL, 1024, 4) == pytest.approx(by_hand / 4)
    # the mixers' shares of the layers' counted work: about 63, 25 and 12%
    linear = 3 * (mixer["linear_projections"] + mixer["delta_scan"])
    full = mixer["full_projections"] + mixer["attn_core"]
    layers = linear + full + 4 * sum(ff.values())
    assert 0.6 < linear / layers < 0.66 and 0.24 < full / layers < 0.27
    m = rows * t * 10 * 16 / 512
    assert flops_delta.expert_matmul_flops_per_step(REAL, 1024, rows) == (
        pytest.approx(2 * 3 * m * 3 * d * 512 * 4))
    assert flops_delta.expert_matmul_min_bytes_per_step(REAL, 1024, rows) == (
        pytest.approx(9 * (m * d + 16 * d * 512 + m * 512) * 4 * 4))
    assert (inspect.signature(flops_delta.expert_matmul_min_seconds)
            == inspect.signature(flops_tokens.expert_matmul_min_seconds))
    # the delta rule: three passes of the chunked form's products; q, k at 16
    # key heads, v, o at 32 value heads, g and beta, and their gradients
    assert flops_delta.delta_scan_flops_per_step(REAL, 1024, rows) == pytest.approx(
        2 * 3 * t // c * 32 * per_chunk * rows * 3)
    qk, v, gates = t * 16 * 128, t * 32 * 128, t * 32
    inputs = 2 * qk + v + 2 * gates
    assert flops_delta.delta_scan_min_bytes_per_step(REAL, 1024, rows) == pytest.approx(
        ((inputs + v) + (inputs + v) + inputs) * 4 * rows * 3)
    least, side = flops_delta.delta_scan_min_seconds(REAL, 1024, rows, 197e12, 819e9)
    assert side == "bytes" and least == pytest.approx(
        flops_delta.delta_scan_min_bytes_per_step(REAL, 1024, rows) / 819e9)


def test_benchmark_json_lists_the_cell_its_files_and_its_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = harness.load_cell(CELL)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert bench["workloads"][-1] is entry  # appended, nothing moved
    assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
        entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert len(entry["why"]) <= 200 and entry["chips"] == 1
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert bench["configs"][-1] is config and len(config["why"]) <= 200
    assert config["file"] == f"benchmark/configs/{REAL}.json"
    assert cell["config_file"]["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cell["config_file"]["source"].startswith(config["source"])
    for key in config["reduced"]:  # the published count stands beside the held one
        assert cell["config_file"]["published"][key] != cell["config_file"][key]
    for key in ("reference", "adapter", "flops"):  # the files the configuration names are there
        assert os.path.exists(os.path.join(ROOT, "benchmark", cell["config_file"][key]))
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == NEW  # appended, in order
    for name in NEW:
        assert listed[name]["workloads"] == [CELL] and listed[name]["moves"] == "pretrain_imgs_per_s"
        doc = harness.load_reader(name).__doc__
        assert f'layer "{listed[name]["layer"]}"' in doc and "pretrain_imgs_per_s" in doc
    for name in SHARED:
        assert listed[name]["workloads"][-1] == CELL
    reports = {m["name"] for m in harness.listed_metrics(CELL, trace=True)}
    assert reports == set(NEW) | set(SHARED)
    # the catalog's numbers, each under its own key, but the three that are cut
    stated = cell["config_file"]
    assert (stated["hidden_size"], stated["linear_num_key_heads"], stated["linear_key_head_dim"],
            stated["linear_num_value_heads"], stated["linear_value_head_dim"],
            stated["linear_conv_kernel_dim"], stated["num_attention_heads"],
            stated["num_key_value_heads"], stated["head_dim"], stated["partial_rotary_factor"],
            stated["moe_intermediate_size"], stated["num_experts_per_tok"],
            stated["shared_expert_intermediate_size"], stated["full_attention_interval"]) == (
        2048, 16, 128, 32, 128, 4, 16, 2, 256, 0.25, 512, 10, 512, 4)
    assert (stated["num_hidden_layers"], stated["num_experts"], stated["vocab_size"]) == (4, 16, 0)


# ------------------------------------------------ the readers on a fixture

P = "jit(ring_update)"
FWD, BWD = "jvp(SupConResNet)", "transpose(jvp(SupConResNet))"
LIN = "encoder/block0/attn/linear_attn/while/body/checkpoint"
HLO = f"""
HloModule jit_ring_update

ENTRY %main.1 (a: f32[8,16]) -> f32[8,16] {{
  %a = f32[8,16]{{1,0}} parameter(0)
  %fusion.1 = f32[8,16]{{1,0}} fusion(%a), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/{LIN}/dot_general"}}
  %fusion.2 = f32[8,16]{{1,0}} fusion(%fusion.1), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/{LIN}/short_conv/mul"}}
  %fusion.3 = f32[8,16]{{1,0}} fusion(%fusion.2), kind=kLoop, calls=%f, metadata={{op_name="{P}/{BWD}/{LIN}/delta_scan/while/body/dot_general"}}
  %fusion.4 = f32[8,16]{{1,0}} fusion(%fusion.3), kind=kLoop, calls=%f, metadata={{op_name="{P}/{BWD}/encoder/block3/attn/while/body/checkpoint/attn_core/exp"}}
  %fusion.5 = f32[8,16]{{1,0}} fusion(%fusion.4), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/encoder/block1/moe/sort"}}
  %fusion.6 = f32[8,16]{{1,0}} fusion(%fusion.5), kind=kLoop, calls=%f, metadata={{op_name="{P}/{BWD}/encoder/block1/moe/shared/dot_general"}}
  %ragged-dot-none.4 = f32[8,16]{{1,0}} custom-call(%fusion.6), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  ROOT %fusion.7 = f32[8,16]{{1,0}} fusion(%ragged-dot-none.4), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/encoder/patch_embed/dot_general"}}
}}
"""
NAMES = ("fusion.1", "fusion.2", "fusion.3", "fusion.4", "fusion.5", "fusion.6",
         "ragged-dot-none.4", "fusion.7")
NS = (10.0, 6.0, 20.0, 8.0, 5.0, 7.0, 30.0, 2.0)


def fixture_run(text, monkeypatch):
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
    monkeypatch.setattr(ds.sr, "program_text", lambda: ("ring_update", text))
    records = [{"name": "bench_window_start", "track": "bench", "ts": 1.0},
               {"name": "health_window", "track": "health", "ts": 2.0,
                "args": {"moe_held_share": 0.11, "delta_decay_mean": 0.05, "step": 10}},
               {"name": "health_window", "track": "health", "ts": 3.0,
                "args": {"moe_held_share": 0.03125, "moe_load_max_over_mean": 1.25,
                         "delta_decay_mean": 0.06, "step": 20}},
               {"name": "bench_window_end", "track": "bench", "ts": 4.0}]
    return {"planes": [plane], "stretches": [(0.0, 200.0, 2)], "worst": 0, "records": records,
            "flops": flops_delta, "config": {"model": REAL}, "size": 1024,
            "global_batch": 4, "chips": 1,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_buckets_by_the_innermost_scope():
    assert ds.scope_map(HLO) == {
        "fusion.1": ("linear_attn", "fwd"), "fusion.2": ("short_conv", "fwd"),
        "fusion.3": ("delta_scan", "bwd"), "fusion.4": ("attn", "bwd"),
        "fusion.5": ("moe", "fwd"), "fusion.6": ("moe", "bwd"),
        # the compiler's own kernel: by its name, direction by its neighbours
        "ragged-dot-none.4": ("experts", "fwd"), "fusion.7": ("embed", "fwd")}
    assert ds.bucket_of(f"{P}/{BWD}/loss/mul") == ("loss", "bwd")
    assert ds.bucket_of("state.params") is None
    assert ds.is_delta_step(HLO)
    import token_scopes as ts  # the accepted machinery is not changed by the rebinding
    assert ts.bucket_of(f"{P}/{FWD}/{LIN}/short_conv/mul") == ("attn", "fwd")


def test_readers_on_the_fixture(monkeypatch):
    run = fixture_run(HLO, monkeypatch)
    read = lambda name: harness.load_reader(name).read(run)  # noqa: E731
    assert read("encoder.linear_attn_ms_per_step") == pytest.approx(1e3 * 36e-9)
    assert read("encoder.delta_scan_ms_per_step") == pytest.approx(1e3 * 20e-9)
    least, _ = flops_delta.delta_scan_min_seconds(REAL, 1024, 8, 197e12, 819e9)
    assert read("encoder.delta_scan_roofline_share") == pytest.approx(100 * least / 20e-9)
    assert read("delta.decay_mean") == 0.06
    # the accepted token readers, under the names they have: every mixer is
    # attention, the expert layer holds its shared expert
    assert read("encoder.attn_ms_per_step") == pytest.approx(1e3 * 44e-9)
    assert read("encoder.moe_ms_per_step") == pytest.approx(1e3 * 42e-9)
    least, _ = flops_delta.expert_matmul_min_seconds(REAL, 1024, 8, 197e12, 819e9)
    assert read("encoder.expert_matmul_roofline_share") == pytest.approx(100 * least / 30e-9)
    assert read("moe.load_max_over_mean") == 1.25 and read("moe.held_share") == 3.125
    got = ds.scope_seconds(run)
    assert "delta_scan" in ds.table(got["by_scope"], got["busy_s"], got["steps"])
    assert sum(got["by_scope"].values()) == pytest.approx(got["busy_s"])


LATENT_HLO = HLO.replace("/linear_attn/", "/").replace("/short_conv/", "/latent/").replace(
    "/delta_scan/", "/attn_core/")
RESNET_HLO = HLO.replace("block0/attn", "layer1_block0/Conv_0").replace(
    "block3/attn", "layer1_block1/Conv_0").replace("block1/moe", "layer2_block0/Conv_1")


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_without_its_scopes(name, monkeypatch):
    """A rehearsal (no trace, no health window), a ResNet's step and
    Moonlight's step under the same readers (the parent commit's side of a
    traced run)."""
    read = harness.load_reader(name).read
    assert read({"records": [], "trace": None, "stretches": None, "flops": flops_delta}) is None
    for text, flops in ((RESNET_HLO, harness.load_module("flops.py")), (RESNET_HLO, flops_delta),
                        (LATENT_HLO, flops_latent), (LATENT_HLO, flops_delta)):
        run = fixture_run(text, monkeypatch)
        run["records"] = [r for r in run["records"] if r["name"] != "health_window"]
        run["flops"] = flops
        assert read(run) is None
