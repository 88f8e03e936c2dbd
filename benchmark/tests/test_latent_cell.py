"""The cell ``moonlight-16b-a3b-ep8.pretrain-1024px-b4``: its rehearsal through
``run.drive`` with the tiny preset (the harness shrinks image and batch, never
the model, so the test names the preset that program and reference both
know); a state left unchanged reads ``correct`` false, and the control
shows in the one number that catches it at the real size;
``flops_latent`` against a count by hand; ``BENCHMARK.json`` lists the cell,
its files and its readers; each new reader silent on a rehearsal and on a
step without its scopes, and a number on a fixture.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import flops_latent
import flops_tokens
import latent_scopes as ls
import run as harness
import trace_reduce as tr

CELL = "moonlight-16b-a3b-ep8.pretrain-1024px-b4"
REAL = "moonlight-16b-a3b-ep8"
TINY = ["--model", "moonlight-tiny"]
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["encoder.latent_ms_per_step", "encoder.attn_core_ms_per_step",
       "encoder.attn_core_roofline_share", "encoder.shared_expert_ms_per_step",
       "encoder.dense_mlp_ms_per_step", "moe.route_bias_max_abs"]
SHARED = ["encoder.attn_ms_per_step", "encoder.moe_ms_per_step",
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
    """``stats_median_diff`` reads the expert layers' ``prob_mean`` alone
    (``reference_latent.stats_order``). At the tiny size, one expert layer of
    width 32 on the CPU's exact products, the control reads 9e-6 where a sound
    rehearsal reads under 1e-7; the cell's limit is set from the chip's
    readings at the real size (sound seeds under 2.4e-5, ``--bf16`` over
    4.6e-5: PERF.md, "How ``correct`` is decided"), so here the control
    stands a hundred times over the sound side and still under that limit."""
    res = rehearse(22, ["--bf16"])
    assert res["compared"]["stats_median_diff"]["value"] > 3e-6
    assert not over_a_limit(res)


def test_flops_against_a_count_by_hand():
    """The published widths over rows of 4,096 tokens."""
    a = flops_latent.reference.arch(REAL)
    t, d = 4096, 2048
    attention = flops_latent.attention_macs_per_row(a, t)
    assert attention["projections"] == t * (d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d)
    assert attention["attn_core"] == t * (t + 1) // 2 * 16 * (192 + 128)
    ff = flops_latent.feed_forward_macs_per_row(a, t)
    assert ff == {"dense": t * 3 * d * 11264, "router": t * d * 64, "shared": t * 3 * d * 2816,
                  "experts": t * 0.75 * 3 * d * 1408}  # 6 of 64, 8 held: three quarters a token
    rows = 8
    per_row = (2 * t * 768 * d + 3 * 5 * sum(attention.values()) + 3 * ff["dense"]
               + 3 * 4 * (ff["router"] + ff["shared"] + ff["experts"]) + 3 * (d * d + d * 128))
    by_hand = 2 * rows * per_row + 3 * 2 * rows * rows * 128
    assert flops_latent.step_flops(REAL, 1024, 4) == pytest.approx(by_hand)
    assert flops_latent.flops_per_image(REAL, 1024, 4) == pytest.approx(by_hand / 4)
    # the grouped products: the same work, counted as flops_tokens counts Keye's
    assert flops_latent.expert_matmul_flops_per_step(REAL, 1024, rows) == (
        pytest.approx(2 * 3 * rows * t * 0.75 * 3 * d * 1408 * 4))
    m = rows * t * 0.75
    assert flops_latent.expert_matmul_min_bytes_per_step(REAL, 1024, rows) == (
        pytest.approx(9 * (m * d + 8 * d * 1408 + m * 1408) * 4 * 4))
    least, side = flops_latent.expert_matmul_min_seconds(REAL, 1024, rows, 197e12, 819e9)
    assert side == "flops" and least == pytest.approx(2 * 3 * m * 3 * d * 1408 * 4 / 197e12)
    import inspect
    assert (inspect.signature(flops_latent.expert_matmul_min_seconds)
            == inspect.signature(flops_tokens.expert_matmul_min_seconds))
    # scores and values: the causal pairs, three passes, against q, k, v, o and their cotangents
    pairs = t * (t + 1) // 2
    assert flops_latent.attn_core_flops_per_step(REAL, 1024, rows) == pytest.approx(
        2 * 3 * pairs * 16 * 320 * rows * 5)
    qk, v = t * 16 * 192, t * 16 * 128
    assert flops_latent.attn_core_min_bytes_per_step(REAL, 1024, rows) == pytest.approx(
        ((2 * qk + 2 * v) + (4 * qk + 4 * v)) * 4 * rows * 5)
    least, side = flops_latent.attn_core_min_seconds(REAL, 1024, rows, 197e12, 819e9)
    assert side == "flops" and least == pytest.approx(2 * 3 * pairs * 16 * 320 * rows * 5 / 197e12)


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
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cell["config_file"]["source"].startswith(config["source"])
    for key in config["reduced"]:  # the published count stands beside the held one
        assert cell["config_file"]["published"][key] != cell["config_file"][key]
    for key in ("reference", "adapter", "flops"):  # the files the configuration names are there
        assert os.path.exists(os.path.join(ROOT, "benchmark", cell["config_file"][key]))
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == NEW  # appended, in the issue's order
    for name in NEW:
        assert listed[name]["workloads"] == [CELL] and listed[name]["moves"] == "pretrain_imgs_per_s"
        doc = harness.load_reader(name).__doc__
        assert f'layer "{listed[name]["layer"]}"' in doc and "pretrain_imgs_per_s" in doc
    for name in SHARED:
        assert listed[name]["workloads"][-1] == CELL
    reports = {m["name"] for m in harness.listed_metrics(CELL, trace=True)}
    assert len(reports) == 22
    assert set(NEW) | set(SHARED) | {"step.mfu.pretrain", "device.peak_mem_share"} <= reports
    assert not {"loss.kernel_ms_per_step", "encoder.conv_ms_per_step",
                "encoder.indexer_ms_per_step", "encoder.attn_kernel_ms_per_step"} & reports
    # the catalog's numbers, each under its own key, but the three that are cut
    stated = cell["config_file"]
    assert (stated["hidden_size"], stated["num_attention_heads"], stated["kv_lora_rank"],
            stated["qk_nope_head_dim"], stated["qk_rope_head_dim"], stated["v_head_dim"],
            stated["intermediate_size"], stated["moe_intermediate_size"],
            stated["num_experts_per_tok"], stated["n_shared_experts"],
            stated["routed_scaling_factor"], stated["first_k_dense_replace"]) == (
        2048, 16, 512, 128, 64, 128, 11264, 1408, 6, 2, 2.446, 1)
    assert (stated["num_hidden_layers"], stated["n_routed_experts"], stated["vocab_size"]) == (5, 8, 0)


# ------------------------------------------------ the readers on a fixture

P = "jit(ring_update)"
FWD, BWD = "jvp(SupConResNet)", "transpose(jvp(SupConResNet))"
HLO = f"""
HloModule jit_ring_update

ENTRY %main.1 (a: f32[8,16]) -> f32[8,16] {{
  %a = f32[8,16]{{1,0}} parameter(0)
  %fusion.1 = f32[8,16]{{1,0}} fusion(%a), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/encoder/block0/attn/while/body/checkpoint/dot_general"}}
  %fusion.2 = f32[8,16]{{1,0}} fusion(%fusion.1), kind=kLoop, calls=%f, metadata={{op_name="{P}/{FWD}/encoder/block0/attn/while/body/checkpoint/latent/dot_general"}}
  %fusion.3 = f32[8,16]{{1,0}} fusion(%fusion.2), kind=kLoop, calls=%f, metadata={{op_name="{P}/{BWD}/encoder/block0/attn/while/body/checkpoint/while/body/checkpoint/attn_core/exp"}}
  %fusion.4 = f32[8,16]{{1,0}} fusion(%fusion.3), kind=kLoop, calls=%f, metadata={{op_name="{P}/{BWD}/encoder/block0/mlp/while/body/checkpoint/dot_general"}}
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
    monkeypatch.setattr(ls.sr, "program_text", lambda: ("ring_update", text))
    records = [{"name": "bench_window_start", "track": "bench", "ts": 1.0},
               {"name": "health_window", "track": "health", "ts": 2.0,
                "args": {"moe_held_share": 0.11, "route_bias_max_abs": 0.01, "step": 10}},
               {"name": "health_window", "track": "health", "ts": 3.0,
                "args": {"moe_held_share": 0.125, "moe_load_max_over_mean": 1.25,
                         "route_bias_max_abs": 0.02, "step": 20}},
               {"name": "bench_window_end", "track": "bench", "ts": 4.0}]
    return {"planes": [plane], "stretches": [(0.0, 200.0, 2)], "worst": 0, "records": records,
            "flops": flops_latent, "config": {"model": REAL}, "size": 1024,
            "global_batch": 4, "chips": 1,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_buckets_by_the_innermost_scope():
    assert ls.scope_map(HLO) == {
        "fusion.1": ("attn", "fwd"), "fusion.2": ("latent", "fwd"), "fusion.3": ("attn_core", "bwd"),
        "fusion.4": ("mlp", "bwd"), "fusion.5": ("moe", "fwd"), "fusion.6": ("shared", "bwd"),
        # the compiler's own kernel: by its name, direction by its neighbours
        "ragged-dot-none.4": ("experts", "fwd"), "fusion.7": ("embed", "fwd")}
    assert ls.bucket_of(f"{P}/{BWD}/loss/mul") == ("loss", "bwd")
    assert ls.bucket_of("state.params") is None
    assert ls.is_latent_step(HLO)


def test_readers_on_the_fixture(monkeypatch):
    run = fixture_run(HLO, monkeypatch)
    read = lambda name: harness.load_reader(name).read(run)  # noqa: E731
    assert read("encoder.latent_ms_per_step") == pytest.approx(1e3 * 6e-9)
    assert read("encoder.attn_core_ms_per_step") == pytest.approx(1e3 * 20e-9)
    assert read("encoder.dense_mlp_ms_per_step") == pytest.approx(1e3 * 8e-9)
    assert read("encoder.shared_expert_ms_per_step") == pytest.approx(1e3 * 7e-9)
    least, _ = flops_latent.attn_core_min_seconds(REAL, 1024, 8, 197e12, 819e9)
    assert read("encoder.attn_core_roofline_share") == pytest.approx(100 * least / 20e-9)
    assert read("moe.route_bias_max_abs") == 0.02
    # the five accepted token readers, under the names they have: attention
    # whole (latent and core inside it), the expert layer with its shared experts
    assert read("encoder.attn_ms_per_step") == pytest.approx(1e3 * 36e-9)
    assert read("encoder.moe_ms_per_step") == pytest.approx(1e3 * 42e-9)
    least, _ = flops_latent.expert_matmul_min_seconds(REAL, 1024, 8, 197e12, 819e9)
    assert read("encoder.expert_matmul_roofline_share") == pytest.approx(100 * least / 30e-9)
    assert read("moe.load_max_over_mean") == 1.25 and read("moe.held_share") == 12.5
    got = ls.scope_seconds(run)
    assert "attn_core" in ls.table(got["by_scope"], got["busy_s"], got["steps"])
    assert sum(got["by_scope"].values()) == pytest.approx(got["busy_s"])


KEYE_HLO = (HLO.replace("/latent/", "/indexer/").replace("/attn_core/", "/indexer/")
            .replace("block0/mlp", "block0/moe").replace("/shared/", "/experts/"))
RESNET_HLO = HLO.replace("block0/attn", "layer1_block0/Conv_0").replace(
    "block0/mlp", "layer1_block1/Conv_0").replace("block1/moe", "layer2_block0/Conv_1")


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_without_its_scopes(name, monkeypatch):
    """A rehearsal (no trace, no health window), a ResNet's step and Keye's
    step under the same readers (the parent commit's side of a traced run)."""
    read = harness.load_reader(name).read
    assert read({"records": [], "trace": None, "stretches": None, "flops": flops_latent}) is None
    for text, flops in ((RESNET_HLO, harness.load_module("flops.py")), (KEYE_HLO, flops_tokens),
                        (KEYE_HLO, flops_latent)):
        run = fixture_run(text, monkeypatch)
        run["records"] = [r for r in run["records"] if r["name"] != "health_window"]
        run["flops"] = flops
        assert read(run) is None
