"""Tests for the scripts/ gate + measurement tooling.

The reference ships no CI tooling at all (SURVEY.md §4); this repo's round
gates (`scripts/ratchet.py`, `scripts/northstar.py`) and PERF.md evidence
(`scripts/crop_ab.py`, `scripts/_honest_timing.py`)
hang off small parsing/summary functions that until now were only exercised
by the full chip runs. A silent parse regression there would let a failing
accuracy gate read as green — worth pinning with fast CPU tests.
"""

import importlib.util
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

SCRIPTS = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "scripts")
)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- ratchet


def test_ratchet_best_acc_takes_last_line(tmp_path):
    ratchet = _load("ratchet")
    log = tmp_path / "probe.log"
    log.write_text(
        "Train: [1][1/7] loss 2.3\n"
        "best accuracy: 41.20\n"
        "noise\n"
        "best accuracy: 96.43\n"
    )
    assert ratchet.best_acc(str(log)) == 96.43


def test_ratchet_best_acc_missing_raises(tmp_path):
    ratchet = _load("ratchet")
    log = tmp_path / "probe.log"
    log.write_text("no accuracy lines here\n")
    with pytest.raises(ratchet.ConfigFailed):
        ratchet.best_acc(str(log))


def test_ratchet_dead_config_emits_record_and_continues(tmp_path, monkeypatch, capsys):
    """The ConfigFailed pattern: one dead config must not skip the remaining
    gates or eat the summary line the CI parses."""
    ratchet = _load("ratchet")

    def fake_run_config(name, spec, epochs, bar, args):
        if name == "rn50_100ep":
            raise ratchet.ConfigFailed("simulated dead config")
        record = {
            "metric": f"ratchet_x_probe_top1_{name}", "value": 97.0,
            "bar": bar, "ok": True,
        }
        print(json.dumps(record), flush=True)
        return record

    monkeypatch.setattr(ratchet, "run_config", fake_run_config)
    monkeypatch.setattr(
        sys, "argv",
        ["ratchet.py", "--configs", "rn50_100ep", "rn18_100ep",
         "--workdir", str(tmp_path)],
    )
    with pytest.raises(SystemExit) as exc:
        ratchet.main()
    assert exc.value.code == 1  # the dead config fails the gate...

    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    summary = lines[-1]
    assert summary["metric"] == "ratchet_gate" and summary["ok"] is False
    # ...but BOTH configs appear in the summary, the dead one with value None
    assert len(summary["configs"]) == 2
    dead = [r for r in lines[:-1] if r.get("value") is None]
    assert len(dead) == 1 and "simulated dead config" in dead[0]["error"]
    assert any(r.get("value") == 97.0 for r in lines[:-1])


# -------------------------------------------------------------- northstar


def test_northstar_parse_probe_log_top5_and_fallback(tmp_path):
    northstar = _load("northstar")
    log = tmp_path / "probe.log"
    log.write_text(
        "best accuracy: 80.00, accuracy5: 99.00\n"
        "best accuracy: 84.76, accuracy5: 99.36\n"
    )
    assert northstar.parse_probe_log(str(log)) == (84.76, 99.36)
    # top1-only fallback (older probe logs)
    log.write_text("best accuracy: 84.76\n")
    assert northstar.parse_probe_log(str(log)) == (84.76, None)
    log.write_text("nothing\n")
    with pytest.raises(northstar.PointFailed):
        northstar.parse_probe_log(str(log))


def test_northstar_newest_run_dir(tmp_path):
    northstar = _load("northstar")
    models = tmp_path / "cifar10_models"
    models.mkdir()
    older = models / "run_a_trial_t_cosine"
    newer = models / "run_b_trial_t_cosine"
    other = models / "run_c_trial_other_cosine_warm"
    for d in (older, newer, other):
        d.mkdir()
    os.utime(older, (1, 1))
    os.utime(newer, (2, 2))
    got = northstar.newest_run_dir(str(tmp_path), "cifar10", "trial_t_cosine")
    assert got == str(newer)
    with pytest.raises(northstar.PointFailed):
        northstar.newest_run_dir(str(tmp_path), "cifar10", "trial_missing")


def test_northstar_published_points_match_baseline():
    """Every number the north star gates against must appear verbatim in
    BASELINE.md's published table (reference README.md:44-45,51-52) — the
    two must not drift apart."""
    northstar = _load("northstar")
    repo = os.path.dirname(SCRIPTS)
    with open(os.path.join(repo, "BASELINE.md")) as f:
        baseline_md = f.read()
    for points in northstar.PUBLISHED.values():
        for top1, top5 in points.values():
            assert f"{top1:.2f}%" in baseline_md
            assert f"{top5:.2f}%" in baseline_md


# ---------------------------------------------------- crop A/B + timing


def test_crop_gather_matches_matmul_crop():
    """The per-pixel-gather reference in scripts/crop_ab.py and the
    production interpolation-matmul crop (ops/augment.py crop_and_resize)
    are the same bilinear sampler — on CPU (fp32 matmuls) they must agree
    to float tolerance, including at the borders."""
    crop_ab = _load("crop_ab")
    from simclr_pytorch_distributed_tpu.ops import augment

    rng = np.random.default_rng(3)
    img = jnp.asarray(rng.random((32, 32, 3), dtype=np.float32))
    boxes = [
        (0.0, 0.0, 32.0, 32.0),    # identity crop
        (5.0, 7.0, 20.0, 13.0),    # interior, non-square
        (0.0, 0.0, 1.0, 1.0),      # degenerate 1x1 crop
        (31.0, 31.0, 1.0, 1.0),    # bottom-right corner
        (10.5, 3.25, 15.5, 21.0),  # fractional origin/size
    ]
    for top, left, h, w in boxes:
        a = augment.crop_and_resize(
            img, jnp.float32(top), jnp.float32(left),
            jnp.float32(h), jnp.float32(w), 32,
        )
        b = crop_ab.crop_and_resize_gather(
            img, jnp.float32(top), jnp.float32(left),
            jnp.float32(h), jnp.float32(w), 32,
        )
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=str((top, left, h, w)))


def test_honest_timing_harness_smoke():
    """time_per_iter runs its chained fori_loop program and returns a
    finite nonnegative per-iteration time."""
    ht = _load("_honest_timing")

    def core(i, lead):
        return jnp.sum(lead) * 1e-20 + jnp.float32(i) * 0.0

    dt = ht.time_per_iter(core, (jnp.ones((16,), jnp.float32),), iters=4, windows=2)
    assert np.isfinite(dt) and dt >= 0.0


def test_honest_timing_rejects_degenerate_iters():
    """iters < 2 cannot subtract the dispatch floor: a bad CLI --iters flag
    must fail with a clear message BEFORE the warmup compiles, not with a
    ZeroDivisionError after them (ADVICE.md round 5)."""
    import pytest

    ht = _load("_honest_timing")

    def core(i, lead):
        return jnp.sum(lead)

    for bad in (1, 0, -3):
        with pytest.raises(ValueError, match="iters must be >= 2"):
            ht.time_per_iter(core, (jnp.ones((4,), jnp.float32),), iters=bad)


def test_crop_ab_patch_brackets_compilation():
    """The pipeline-level A/B patches augment.crop_and_resize at the
    make_core level (_patched_crop), so EVERY trace of the timed program —
    including re-traces from jit cache misses — sees the selected backend
    (ADVICE.md round 5: an inside-the-core patch only covered the first
    trace)."""
    import jax

    crop_ab = _load("crop_ab")
    from simclr_pytorch_distributed_tpu.ops import augment

    orig = augment.crop_and_resize
    seen = []

    def fake_crop(img, top, left, h, w, out_size):
        seen.append(1)
        return orig(img, top, left, h, w, out_size)

    core = crop_ab._pipeline_core(fake_crop)
    imgs = jnp.ones((2, 32, 32, 3), jnp.float32) * 128.0
    with crop_ab._patched_crop(fake_crop):
        assert augment.crop_and_resize is fake_crop
        out = core(0, imgs, jax.random.key(0))
        assert np.isfinite(float(out))
    assert augment.crop_and_resize is orig  # restored after the window
    assert seen  # the selected backend was actually traced

    # outside the patch window the core refuses to run (the trace would
    # silently time the production backend)
    with pytest.raises(AssertionError, match="_patched_crop"):
        core(0, imgs, jax.random.key(0))


# ---------------------------------------------------------- h2d_overlap_ab


def test_h2d_build_output_single_run_keeps_variants_schema():
    h2d = _load("h2d_overlap_ab")
    records = [{"resident": 64.5, "put_then_step": 70.1, "step_then_put": 66.0}]
    glitched = [{"resident": 0, "put_then_step": 1, "step_then_put": 0}]
    out = h2d.build_output(256, "cpu", records, glitched)
    assert out["variants"] == records[0]
    assert out["windows_discarded_as_clock_glitch"] == glitched[0]
    assert "runs" not in out


def test_h2d_build_output_multi_run_emits_committed_schema():
    """--runs N must emit the {runs: [...]} multi-run schema (ADVICE.md
    round 5: an artifact had been hand-assembled from a schema the script
    never produced)."""
    h2d = _load("h2d_overlap_ab")
    records = [
        {"resident": 64.5, "put_then_step": 70.1, "step_then_put": 66.0},
        {"resident": 64.8, "put_then_step": 69.0, "step_then_put": 74.4},
        {"resident": 65.1, "put_then_step": 65.0, "step_then_put": 65.7},
    ]
    glitched = [{"resident": 0, "put_then_step": 1, "step_then_put": 0}] * 3
    out = h2d.build_output(256, "TPU v5 lite", records, glitched)
    assert out["runs"] == records and "variants" not in out
    assert out["windows_discarded_as_clock_glitch"] == 3  # summed, as committed
    assert out["metric"] == "h2d_overlap_ab_step_ms" and out["batch"] == 256
    # the multi-run schema's key set, exactly
    assert set(out) == {
        "batch", "device", "metric", "note", "runs",
        "windows_discarded_as_clock_glitch",
    }


# ------------------------------------------------------------- serve_bench


@pytest.mark.serve
def test_serve_bench_smoke_end_to_end(tmp_path):
    """The acceptance run: engine → batcher → cache → HTTP endpoint on CPU,
    artifact written, no recompiles within buckets, cache pass skipped the
    engine."""
    serve_bench = _load("serve_bench")
    out_path = tmp_path / "serve_bench_smoke.json"
    out = serve_bench.main(["--smoke", "--json", str(out_path)])

    with open(out_path) as f:
        artifact = json.load(f)
    assert artifact == json.loads(json.dumps(out))  # what returned is what landed
    assert artifact["metric"] == "serve_bench" and artifact["mode"] == "smoke"
    # one compile per bucket, ever — request sizes varied within buckets
    assert all(n == 1 for n in artifact["engine_stats"]["traces"].values())
    assert set(artifact["engine_stats"]["traces"]) == {"2", "8"}
    # both loops produced latency populations with sane percentiles
    for loop in ("closed_loop", "open_loop"):
        assert artifact[loop]["requests"] > 0
        for pcts in artifact[loop]["latency_by_bucket"].values():
            assert pcts["p50_ms"] <= pcts["p95_ms"] <= pcts["p99_ms"]
    # the cache answered the duplicate pass without touching the engine
    assert artifact["cache"]["hit_rows"] == 4
    assert artifact["cache"]["extra_dispatches"] == 0
    # the real HTTP endpoint served /healthz, /embed (both encodings), /stats
    assert artifact["http"]["healthz"] == "ok"
    assert artifact["http"]["embed_n"] == 2 and artifact["http"]["embed_dim"] == 512
    assert artifact["http"]["encodings_agree"] is True
    assert artifact["batcher_stats"]["errors"] == 0


@pytest.mark.serve
def test_serve_bench_sweep_smoke_end_to_end(tmp_path):
    """The saturation-sweep acceptance run on CPU: both comparison arms
    (synchronous baseline, pipelined) climb the offered-rate ladder through
    the REAL assembler -> inflight window -> completer stack, per-window
    inflight gauges land in the artifact, and the pipelined arm PROVABLY
    held >1 batch in flight while the baseline never did."""
    serve_bench = _load("serve_bench")
    out_path = tmp_path / "serve_bench_sweep_smoke.json"
    out = serve_bench.main(["--smoke", "--sweep", "--json", str(out_path)])

    with open(out_path) as f:
        artifact = json.load(f)
    assert artifact == json.loads(json.dumps(out))
    assert artifact["metric"] == "serve_bench_sweep"
    assert artifact["mode"] == "smoke"
    for arm, inflight in (("baseline", 1), ("pipelined", 3)):
        a = artifact[arm]
        assert a["max_inflight"] == inflight
        assert len(a["windows"]) >= 1
        assert a["saturated_imgs_per_s"] > 0
        for w in a["windows"]:
            assert w["requests_completed"] > 0
            assert w["latency"]["p50_ms"] <= w["latency"]["p99_ms"]
            assert 0.0 <= w["inflight"]["pipeline_occupancy"] <= 1.0
            assert (
                w["inflight"]["dispatched_batches"]
                >= w["inflight"]["batches"]
            )
    # the pipelined arm really pipelined; the baseline arm never could
    assert max(
        w["inflight"]["max_inflight_observed"]
        for w in artifact["pipelined"]["windows"]
    ) > 1
    assert all(
        w["inflight"]["max_inflight_observed"] <= 1
        for w in artifact["baseline"]["windows"]
    )
    # one compile per bucket ACROSS both arms and the HTTP round trip —
    # the ladder never re-traced
    assert artifact["engine_stats"]["traces"] == {"2": 1, "8": 1}
    assert artifact["http"]["healthz"] == "ok"
    assert artifact["saturated_speedup"] > 0
    # the mixed-tenant multi-model arm: both hosted versions served their
    # skewed tenant's requests through the registry with zero errors
    mm = artifact["multi_model"]
    assert mm["tenancy"] == {"bulk": "prod", "interactive": "canary"}
    assert mm["requests"] > 0 and mm["throughput_imgs_per_s"] > 0
    per_model = mm["per_model"]
    assert set(per_model) == {"prod", "canary"}
    assert per_model["prod"]["requests"] > per_model["canary"]["requests"]
    for m in per_model.values():
        assert m["errors"] == 0
        if m["latency"]:
            assert m["latency"]["p50_ms"] <= m["latency"]["p99_ms"]
    assert mm["admission"]["rejected"] == 0  # quota disabled in the bench
    # the retrieval arm: closed-loop /neighbors under mixed /embed load,
    # once per impl rung on the SAME workload stream — the IVF arm reached
    # the trained path (not just the provisional single-list rung) and
    # both indexes ingested the identical corpus
    ra = artifact["retrieval"]
    assert set(ra["per_impl"]) == {"brute", "ivf"}
    brute, ivf = ra["per_impl"]["brute"], ra["per_impl"]["ivf"]
    assert brute["index"]["entries"] == ivf["index"]["entries"] > 0
    assert brute["neighbors_queries"] == ivf["neighbors_queries"] > 0
    for arm in (brute, ivf):
        assert arm["index"]["queries"] == arm["neighbors_queries"]
        assert arm["query_latency"]["p50_ms"] <= arm["query_latency"]["p99_ms"]
    assert ivf["index"]["trained_lists"] == ra["nlist"]
    assert ivf["index"]["retrains"] >= 1
    # early queries land on the untrained single-list rung (1 probe each),
    # later ones fan out to nprobe lists
    assert (ivf["neighbors_queries"] <= ivf["index"]["probes"]
            <= ra["nprobe"] * ivf["neighbors_queries"])
    assert ra["query_p50_ratio_brute_over_ivf"] is not None


# ------------------------------------------------------------ retrieval_ab


def _retrieval_rung(rows, recall=1.0, speedup=6.0):
    return {
        "rows": rows, "recall_at_k": recall, "speedup_p50": speedup,
        "insert_ms": {"brute": 1.0, "ivf": 2.0}, "runs": [],
        "lat_ms": {"brute": {"p50": 10.0, "p99": 20.0, "n": 16},
                   "ivf": {"p50": 2.0, "p99": 4.0, "n": 16}},
        "ivf_stats": {"trained_lists": 8, "retrains": 1},
    }


def test_retrieval_ab_build_output_schema():
    """The committed docs/evidence/retrieval_ab_r18.json schema, pinned
    without building a 262144-row index (the window_ab pattern)."""
    retrieval_ab = _load("retrieval_ab")
    rungs = [_retrieval_rung(4096), _retrieval_rung(65536, 0.98, 50.0)]
    oracle = {"ids_identical": True, "scores_bit_identical": True,
              "queries_checked": 32, "rungs_checked": [4096, 65536]}
    out = retrieval_ab.build_output(
        "cpu", {"dim": 64, "k": 10, "nprobe": 8}, rungs, oracle
    )
    assert out["schema"] == retrieval_ab.SCHEMA == "retrieval_ab/v1"
    assert out["metric"] == "retrieval_query_ms"
    assert "ABBA" in out["arm_order"]
    s = out["summary"]
    assert s["min_recall_at_k"] == 0.98
    assert s["max_rung_rows"] == 65536 and s["speedup_p50_max_rung"] == 50.0
    assert s["recall_bar"] == retrieval_ab.RECALL_BAR
    assert [r["rows"] for r in s["per_rung"]] == [4096, 65536]
    with open(os.path.join(
        os.path.dirname(SCRIPTS), "docs", "evidence", "retrieval_ab_r18.json"
    )) as f:
        committed = json.load(f)
    assert set(out) == set(committed)


def test_retrieval_ab_smoke_oracle_and_recall(tmp_path):
    """The real A/B end-to-end on tiny rungs: both indexes built from the
    same chunked insert stream, the brute arm bit-checked against the
    frozen PR-17 scoring oracle on EVERY rung before any timing, IVF
    recall measured against the brute answers, artifact committed."""
    retrieval_ab = _load("retrieval_ab")
    out_path = tmp_path / "retrieval_ab.json"
    out = retrieval_ab.main(["--smoke", "--json", str(out_path)])
    artifact = json.loads(out_path.read_text())
    assert artifact == json.loads(json.dumps(out))
    assert artifact["schema"] == "retrieval_ab/v1"
    oracle = artifact["oracle"]
    assert oracle["ids_identical"] and oracle["scores_bit_identical"]
    assert oracle["rungs_checked"] == [1024, 4096]
    assert oracle["queries_checked"] > 0
    # clustered smoke corpora: the trained quantizer holds the recall bar
    assert artifact["summary"]["min_recall_at_k"] >= 0.95
    top = max(artifact["rungs"], key=lambda r: r["rows"])
    assert top["ivf_stats"]["trained_lists"] > 1  # not the provisional rung
    assert top["speedup_p50"] > 0


# ----------------------------------------------------------------- flush_ab


def test_flush_ab_build_output_schema():
    """The committed docs/evidence/flush_ab_r6.json schema, pinned without
    running the measurement (the h2d_overlap_ab pattern)."""
    flush_ab = _load("flush_ab")
    rounds = [
        {"sync": [12.0, 11.8], "async": [7.1, 7.0]},
        {"sync": [12.4, 12.2], "async": [7.3, 6.9]},
    ]
    out = flush_ab.build_output("cpu", 60.0, 10, 3, rounds)
    assert out["metric"] == "flush_ab_ms_per_step"
    assert out["runs"] == rounds
    assert out["delay_ms"] == 60.0 and out["window"] == 10
    s = out["summary"]
    assert s["sync_ms_per_step"] == 12.1  # median of 4 sync measurements
    assert s["async_ms_per_step"] == 7.05
    assert s["stall_removed_ms_per_window"] == round((12.1 - 7.05) * 10, 1)
    assert s["speedup"] == round(12.1 / 7.05, 3)
    assert "ABBA" in out["arm_order"]


@pytest.mark.slow
def test_flush_ab_smoke_async_removes_stall(tmp_path):
    """End-to-end CPU proxy: with an injected per-flush transfer delay the
    async arm must be strictly faster per step than the sync arm (the whole
    point of the background executor) — same compiled update both arms."""
    flush_ab = _load("flush_ab")
    out_path = tmp_path / "flush_ab.json"
    out = flush_ab.main(["--smoke", "--rounds", "1", "--json", str(out_path)])
    s = out["summary"]
    # the sync arm pays delay_ms per window on the dispatch thread; the
    # async arm amortizes one drain-tail delay per arm. Require at least
    # half the injected stall to vanish (generous vs timer noise).
    assert s["async_ms_per_step"] < s["sync_ms_per_step"]
    assert s["stall_removed_ms_per_window"] > out["delay_ms"] / 2
    assert json.loads(out_path.read_text())["metric"] == "flush_ab_ms_per_step"


# --------------------------------------------------------------- resident_ab


def test_resident_ab_build_output_schema():
    """The resident_ab artifact schema, pinned without
    running the measurement (the flush_ab/h2d_overlap_ab pattern)."""
    resident_ab = _load("resident_ab")
    rounds = [
        {"host": [300.0, 310.0], "device": [150.0, 148.0]},
        {"host": [305.0, 295.0], "device": [151.0, 149.0]},
    ]
    eq = {"equivalence_ok": True, "steps_compared": 16, "epochs": 2,
          "mid_epoch_resume_checked": True}
    out = resident_ab.build_output("cpu", 200.0, 8, 2, rounds, eq)
    assert out["metric"] == "resident_ab_ms_per_step"
    assert out["runs"] == rounds and out["equivalence"] == eq
    assert out["h2d_delay_ms"] == 200.0 and out["steps_per_epoch"] == 8
    s = out["summary"]
    assert s["host_ms_per_step"] == 302.5  # median of the 4 host arms
    assert s["device_ms_per_step"] == 149.5
    assert s["transfer_removed_ms_per_step"] == 153.0
    assert s["speedup"] == round(302.5 / 149.5, 3)
    assert "ABBA" in out["arm_order"]


@pytest.mark.resident
def test_resident_ab_smoke_device_arm_removes_per_step_transfer(tmp_path):
    """Tier-1 guard on the committed-artifact path (the serve_bench smoke
    pattern): the real script end-to-end on a tiny config — equivalence pass
    (byte-identical batches incl. mid-epoch resume), both compiled arms, the
    ABBA loop, and the JSON artifact. Under the injected serialized-link
    delay the device arm pays it once per EPOCH instead of once per STEP, so
    most of the per-step delay must vanish."""
    resident_ab = _load("resident_ab")
    out_path = tmp_path / "resident_ab.json"
    out = resident_ab.main([
        "--smoke", "--rounds", "1", "--steps", "4", "--epochs", "1",
        "--h2d_delay_ms", "120", "--json", str(out_path),
    ])
    assert out["equivalence"]["equivalence_ok"]
    assert out["equivalence"]["steps_compared"] == 8  # 2 epochs x 4 steps
    s = out["summary"]
    assert s["device_ms_per_step"] < s["host_ms_per_step"]
    # expected removal ~= delay * (1 - 1/steps) = 90 ms at these settings;
    # require a third of the delay (generous vs 1-core contention noise)
    assert s["transfer_removed_ms_per_step"] > out["h2d_delay_ms"] / 3
    artifact = json.loads(out_path.read_text())
    assert artifact["metric"] == "resident_ab_ms_per_step"
    assert artifact["equivalence"]["equivalence_ok"]


# --------------------------------------------------------------- window_ab


def test_window_ab_build_output_schema():
    """The window_ab artifact schema, pinned without
    running the measurement (the resident_ab/flush_ab pattern)."""
    window_ab = _load("window_ab")
    rounds = [
        {"host": [250.0, 260.0], "window": [100.0, 98.0]},
        {"host": [255.0, 245.0], "window": [101.0, 99.0]},
    ]
    eq = {"equivalence_ok": True, "steps_compared": 16, "epochs": 2,
          "mid_epoch_resume_checked": True}
    out = window_ab.build_output("cpu", 200.0, 8, 4, 2, rounds, eq)
    assert out["metric"] == "window_ab_ms_per_step"
    assert out["runs"] == rounds and out["equivalence"] == eq
    assert out["h2d_delay_ms"] == 200.0 and out["steps_per_epoch"] == 8
    assert out["window_batches"] == 4
    s = out["summary"]
    assert s["host_ms_per_step"] == 252.5  # median of the 4 host arms
    assert s["window_ms_per_step"] == 99.5
    assert s["transfer_removed_ms_per_step"] == 153.0
    assert s["speedup"] == round(252.5 / 99.5, 3)
    assert "ABBA" in out["arm_order"]
    # the artifact schema's key set, exactly
    assert set(out) == {
        "arm_order", "device", "epochs_per_arm", "equivalence",
        "h2d_delay_ms", "metric", "note", "runs", "steps_per_epoch",
        "summary", "window_batches",
    }


@pytest.mark.window
def test_window_ab_smoke_window_arm_amortizes_per_step_transfer(tmp_path):
    """Tier-1 guard on the committed-artifact path (the resident_ab smoke
    pattern): the real script end-to-end on a tiny config — equivalence
    pass (byte-identical batches incl. the window+offset mid-epoch resume),
    both compiled arms, the ABBA loop, and the JSON artifact. Under the
    injected serialized-link delay the window arm pays it once per WINDOW
    instead of once per STEP, so most of the per-step delay must vanish."""
    window_ab = _load("window_ab")
    out_path = tmp_path / "window_ab.json"
    out = window_ab.main([
        "--smoke", "--rounds", "1", "--steps", "4", "--epochs", "1",
        "--h2d_delay_ms", "120", "--json", str(out_path),
    ])
    assert out["equivalence"]["equivalence_ok"]
    assert out["equivalence"]["steps_compared"] == 8  # 2 epochs x 4 steps
    s = out["summary"]
    assert s["window_ms_per_step"] < s["host_ms_per_step"]
    # expected removal ~= delay * (1 - 1/window_batches) = 90 ms at these
    # settings (W=4); require a third of the delay (generous vs 1-core
    # contention noise)
    assert s["transfer_removed_ms_per_step"] > out["h2d_delay_ms"] / 3
    artifact = json.loads(out_path.read_text())
    assert artifact["metric"] == "window_ab_ms_per_step"
    assert artifact["equivalence"]["equivalence_ok"]


# ------------------------------------------------------- ratchet bench gate


def test_ratchet_parse_bench_json_takes_last_metric_line(tmp_path):
    ratchet = _load("ratchet")
    log = tmp_path / "bench.log"
    log.write_text(
        "warmup noise\n"
        '{"run": 0, "variant": "x"}\n'
        '{"metric": "pretrain_imgs_per_sec_per_chip", "value": 100.0}\n'
        "not json {\n"
        '{"metric": "pretrain_imgs_per_sec_per_chip", "value": 4100.2, '
        '"vs_baseline": 1.0083}\n'
    )
    rec = ratchet.parse_bench_json(str(log))
    assert rec["value"] == 4100.2 and rec["vs_baseline"] == 1.0083

    (tmp_path / "empty.log").write_text("nothing\n")
    with pytest.raises(ratchet.ConfigFailed):
        ratchet.parse_bench_json(str(tmp_path / "empty.log"))


def test_ratchet_bench_gate_bar_and_config():
    """The perf bar (VERDICT #6) rides the default config list and its bar
    is 95% of the RECORDED repo baseline — bench.py and ratchet.py must
    agree on the number (single source of truth in bench.REPO_BASELINES)."""
    ratchet = _load("ratchet")
    import bench

    assert "bench_pretrain" in ratchet.CONFIGS
    spec = ratchet.CONFIGS["bench_pretrain"]
    assert spec["kind"] == "bench"
    # ONE series name for success and ConfigFailed records alike
    assert ratchet.bench_metric_name(spec) == (
        "ratchet_bench_pretrain_imgs_per_sec_per_chip"
    )
    assert bench.REPO_BASELINES["pretrain"] == 4066.5  # BENCH_r05 headline
    assert ratchet._bench_bar() == round(0.95 * 4066.5, 1)
    # vs_baseline now reads the recorded baseline, not the hardcoded 1.0
    assert bench.vs_baseline_for("pretrain", 4066.5) == 1.0
    assert bench.vs_baseline_for("pretrain", 2033.25) == 0.5
    assert bench.vs_baseline_for("linear", 999.0) == 1.0  # no record yet


def test_ratchet_bench_gate_decision():
    """The gate only enforces the chip-specific bar ON the baseline chip;
    elsewhere it pass-skips with the reason on record. On the baseline chip
    a clock_suspect run fails even above the bar — an inflated number must
    not mask a regression."""
    ratchet = _load("ratchet")
    import bench

    spec = ratchet.CONFIGS["bench_pretrain"]
    kind = bench.REPO_BASELINE_DEVICE_KIND

    def rec(value, device_kind, clock_suspect=False, chips=1):
        return {"value": value, "vs_baseline": 1.0,
                "detail": {"device_kind": device_kind, "chips": chips,
                           "clock_suspect": clock_suspect}}

    bar = 3863.2
    r = ratchet.bench_gate_record(spec, rec(4000.0, kind), bar)
    assert r["ok"] and "skipped" not in r
    r = ratchet.bench_gate_record(spec, rec(3000.0, kind), bar)
    assert not r["ok"]
    # above the bar but the clock is suspect: fail, never certify
    r = ratchet.bench_gate_record(spec, rec(6000.0, kind, clock_suspect=True),
                                  bar)
    assert not r["ok"] and "clock_suspect" in r["error"]
    # a different accelerator: the v5-lite bar is not comparable — pass-skip
    r = ratchet.bench_gate_record(spec, rec(100.0, "TPU v4"), bar)
    assert r["ok"] and "not comparable" in r["skipped"]
    # same kind but multi-chip: the 1-chip baseline's per-chip workload is
    # 256 imgs/chip; a sharded 32/chip run sits below the bar with no real
    # regression (bench_perchip32_r5.json: 3294.5) — pass-skip, never fail
    r = ratchet.bench_gate_record(spec, rec(3294.5, kind, chips=8), bar)
    assert r["ok"] and "not comparable" in r["skipped"]


def test_ratchet_resident_gate_decision():
    """The placement-equivalence gate rides the default config list.
    Bit-identity (equivalence_ok) binds on EVERY device — it is the
    hardware-independent contract that carries accuracy ratchets across
    placements; the timing claim binds only on CPU where the injected
    serialized-link delay is the calibrated proxy (elsewhere: pass-skip
    with the reason on record, the bench gate's device-kind convention)."""
    ratchet = _load("ratchet")
    assert "resident_ab" in ratchet.CONFIGS
    assert ratchet.CONFIGS["resident_ab"]["kind"] == "resident_ab"

    def art(device="cpu", host=300.0, dev=150.0, eq=True):
        return {
            "summary": {"host_ms_per_step": host, "device_ms_per_step": dev},
            "equivalence": {"equivalence_ok": eq, "steps_compared": 16},
            "device": device,
        }

    r = ratchet.resident_gate_record(art())
    assert r["ok"] and "skipped" not in r
    # broken bit-identity fails EVERYWHERE, even where timing pass-skips
    r = ratchet.resident_gate_record(art(device="TPU v4", eq=False))
    assert not r["ok"] and "differ" in r["error"]
    # an accelerator: equivalence enforced, CPU-calibrated timing skipped
    # (even a slower device arm does not fail there)
    r = ratchet.resident_gate_record(art(device="TPU v4", host=64.9, dev=65.2))
    assert r["ok"] and "calibrated" in r["skipped"]
    # on CPU the timing claim binds: the device arm must beat the host arm
    r = ratchet.resident_gate_record(art(host=150.0, dev=150.0))
    assert not r["ok"] and "not faster" in r["error"]


def test_ratchet_window_gate_decision():
    """The WINDOWED placement equivalence gate rides the default config
    list with the resident_ab conventions: bit-identity binds on EVERY
    device, the CPU-calibrated injected-delay timing claim pass-skips
    off-CPU with the reason on record."""
    ratchet = _load("ratchet")
    assert "window_ab" in ratchet.CONFIGS
    assert ratchet.CONFIGS["window_ab"]["kind"] == "window_ab"

    def art(device="cpu", host=250.0, win=100.0, eq=True):
        return {
            "summary": {"host_ms_per_step": host, "window_ms_per_step": win},
            "equivalence": {"equivalence_ok": eq, "steps_compared": 16},
            "window_batches": 4,
            "device": device,
        }

    r = ratchet.window_gate_record(art())
    assert r["ok"] and "skipped" not in r
    assert r["metric"] == "ratchet_window_ab_equivalence"
    # broken bit-identity fails EVERYWHERE, even where timing pass-skips
    r = ratchet.window_gate_record(art(device="TPU v4", eq=False))
    assert not r["ok"] and "differ" in r["error"]
    # an accelerator: equivalence enforced, CPU-calibrated timing skipped
    r = ratchet.window_gate_record(art(device="TPU v4", host=64.9, win=65.2))
    assert r["ok"] and "calibrated" in r["skipped"]
    # on CPU the timing claim binds: the window arm must beat the host arm
    r = ratchet.window_gate_record(art(host=100.0, win=100.0))
    assert not r["ok"] and "not faster" in r["error"]


def test_ratchet_retrieval_gate_decision():
    """The retrieval A/B gate rides the default list: brute bit-identity
    to the PR-17 oracle and the per-rung recall bar bind on EVERY device;
    the CPU-calibrated p50-speedup bar at the top rung pass-skips
    off-CPU with the reason on record."""
    ratchet = _load("ratchet")
    assert "retrieval_ab" in ratchet.CONFIGS
    assert ratchet.CONFIGS["retrieval_ab"]["kind"] == "retrieval_gate"

    def art(device="cpu", recall=(1.0, 0.97), speedup=6.0, ids=True,
            bits=True, checked=None, bar=0.95):
        rungs = [{"rows": rows, "recall_at_k": rc}
                 for rows, rc in zip((4096, 262144), recall)]
        return {
            "schema": "retrieval_ab/v1",
            "rungs": rungs,
            "oracle": {"ids_identical": ids, "scores_bit_identical": bits,
                       "rungs_checked": (
                           checked if checked is not None else [4096, 262144]
                       )},
            "summary": {"recall_bar": bar, "speedup_bar": 5.0,
                        "min_recall_at_k": min(recall),
                        "max_rung_rows": 262144,
                        "speedup_p50_max_rung": speedup},
            "device": device,
        }

    r = ratchet.retrieval_gate_record(art())
    assert r["ok"] and "skipped" not in r
    assert r["metric"] == "ratchet_retrieval_ab" and r["value"] == 6.0
    # the oracle bind is hardware-independent: broken bit-identity fails
    # even where the timing claim would pass-skip
    r = ratchet.retrieval_gate_record(art(device="TPU v4", bits=False))
    assert not r["ok"] and "bitwise" in r["error"]
    r = ratchet.retrieval_gate_record(art(ids=False))
    assert not r["ok"] and "ids diverge" in r["error"]
    # ...and so is the recall bar, naming the offending rung
    r = ratchet.retrieval_gate_record(art(device="TPU v4", recall=(1.0, 0.9)))
    assert not r["ok"] and "262144" in r["error"]
    # the oracle must have covered every rung in the artifact
    r = ratchet.retrieval_gate_record(art(checked=[4096]))
    assert not r["ok"] and "every rung" in r["error"]
    # off-CPU: the CPU-calibrated speedup claim pass-skips
    r = ratchet.retrieval_gate_record(art(device="TPU v4", speedup=1.0))
    assert r["ok"] and "calibrated" in r["skipped"]
    # on CPU the artifact's own speedup bar binds at the top rung
    r = ratchet.retrieval_gate_record(art(speedup=4.0))
    assert not r["ok"] and "5.0x bar" in r["error"]
    # degenerate artifacts never pass silently
    assert not ratchet.retrieval_gate_record({"schema": "nope"})["ok"]
    thin = art()
    thin["rungs"] = thin["rungs"][:1]
    assert "two corpus-size rungs" in ratchet.retrieval_gate_record(thin)["error"]
    bare = art(bar=None)
    bare["summary"]["recall_bar"] = None
    assert "no recall bar" in ratchet.retrieval_gate_record(bare)["error"]


# ------------------------------------------------------------------ hygiene


def test_no_binaries_or_pycache_tracked():
    """VERDICT #7: the compiled .so (and any __pycache__/.pyc) must never be
    committed — native/build.py compiles on demand."""
    import subprocess

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    if not os.path.isdir(os.path.join(repo, ".git")):
        pytest.skip("not a git checkout")
    try:
        tracked = subprocess.run(
            ["git", "ls-files"], cwd=repo, capture_output=True, text=True,
            timeout=60, check=True,
        ).stdout.splitlines()
    except (OSError, subprocess.SubprocessError):
        pytest.skip("git unavailable")
    offenders = [
        f for f in tracked
        if f.endswith((".so", ".pyc")) or "__pycache__" in f
    ]
    assert not offenders, offenders
    gitignore = open(os.path.join(repo, ".gitignore")).read()
    assert "*.so" in gitignore and "__pycache__/" in gitignore


# ------------------------------------------------------------ trace_report


def _span(name, track, ts, dur, **args):
    e = {"name": name, "track": track, "ph": "X", "ts": ts, "dur": dur}
    if args:
        e["args"] = args
    return e


def _instant(name, track, ts, **args):
    e = {"name": name, "track": track, "ph": "i", "ts": ts}
    if args:
        e["args"] = args
    return e


def _good_events():
    """A consistent synthetic run: wall 100s, phases partitioning part of
    it, the rest steady-state."""
    return [
        _instant("run_start", "events", 0.0),
        _span("epoch", "main:epoch", 0.0, 100.0, epoch=1),  # envelope
        _span("first_step", "main:compile", 1.0, 40.0),
        _span("epoch_gather", "main:data", 0.2, 0.5),
        _span("flush_boundary", "main:flush", 50.0, 2.0, dispatch_s=8.0,
              dispatch_min_s=0.5, dispatch_max_s=1.25),
        _span("flush_boundary", "main:flush", 60.0, 2.0, dispatch_s=7.5,
              dispatch_min_s=0.4, dispatch_max_s=3.5),
        _span("flush_boundary", "main:flush", 70.0, 2.0),  # nothing timed
        _span("checkpoint_save", "main:checkpoint", 90.0, 5.0),
        _span("flush_job", "telemetry:flush", 50.5, 8.0),  # other thread
        _instant("run_end", "events", 100.0),
    ]


def test_trace_report_attribution_partitions_wall(tmp_path):
    tr = _load("trace_report")
    report = tr.build_report(_good_events())
    cons = report["consistency"]
    assert cons["wall_s"] == pytest.approx(100.0)
    # compile 40 + data 0.5 + flush 6 + checkpoint 5 = 51.5 attributed
    assert cons["attributed_s"] == pytest.approx(51.5)
    assert cons["steady_state_s"] == pytest.approx(48.5)
    # the remainder, split by the boundaries' dispatch counters
    assert report["steady_state"]["dispatch_s"] == pytest.approx(15.5)
    assert report["steady_state"]["rest_s"] == pytest.approx(33.0)
    # the longest single call of the run, in the max_ms column
    assert report["steady_state"]["dispatch_max_ms"] == pytest.approx(3500.0)
    row = next(ln for ln in tr.render_table(report).splitlines()
               if ln.startswith("  dispatch"))
    assert row.split()[-1] == "3500.0"
    assert cons["monotone_ok"] and cons["nonnegative_ok"] and cons["ok"]
    assert set(report["phases"]) == {"compile", "data", "flush", "checkpoint"}
    assert report["phases"]["flush"]["count"] == 3
    assert report["phases"]["flush"]["mean_ms"] == pytest.approx(2000.0)
    # shares + steady share sum to 1
    total = sum(p["share"] for p in report["phases"].values())
    assert total + report["steady_state"]["share"] == pytest.approx(1.0, abs=1e-3)
    # the epoch envelope and the telemetry-thread job are NOT attributed
    assert "epoch" not in report["phases"]
    # compile at 40% of wall stays under the 50% advisory bar
    assert not any(a["phase"] == "compile" for a in report["anomalies"])


def test_trace_report_flags_overlapping_spans():
    tr = _load("trace_report")
    events = _good_events() + [
        # overlaps the 50.0-52.0 flush boundary ON another main track:
        # main-thread phases may never overlap across tracks either
        _span("checkpoint_save", "main:checkpoint", 51.0, 3.0),
    ]
    report = tr.build_report(events)
    assert not report["consistency"]["monotone_ok"]
    assert not report["consistency"]["ok"]


def test_trace_report_anomaly_flags_and_event_findings():
    tr = _load("trace_report")
    events = [
        _span("first_step", "main:compile", 0.0, 80.0),  # 80% of wall
        _span("flush_boundary", "main:flush", 90.0, 1.0),
        _instant("stall_detected", "watchdog", 95.0, dump=1),
        _instant("nan_rollback", "main:guard", 96.0, epoch=3),
        _instant("end", "events", 100.0),
    ]
    report = tr.build_report(events)
    flags = {a["phase"]: a["flag"] for a in report["anomalies"]}
    assert "compile" in flags  # 80% > 50% advisory bar
    joined = " ".join(a["flag"] for a in report["anomalies"])
    assert "stall watchdog fired" in joined and "NaN rollback" in joined


def test_trace_report_empty_events_raise():
    tr = _load("trace_report")
    with pytest.raises(ValueError):
        tr.build_report([])


def test_trace_report_cli_writes_artifact(tmp_path):
    tr = _load("trace_report")
    events_path = tmp_path / "events.jsonl"
    with open(events_path, "w") as f:
        for e in _good_events():
            f.write(json.dumps(e) + "\n")
    out = tmp_path / "report.json"
    rc = tr.main(["--events", str(events_path), "--json", str(out)])
    assert rc == 0
    artifact = json.load(open(out))
    assert artifact["schema"] == "trace_report/v1"
    assert artifact["report"]["consistency"]["ok"]
    # the rendered table reached stdout is covered by rc; pin the artifact
    # keys the ratchet gate consumes
    assert {"phases", "steady_state", "anomalies", "consistency",
            "n_events"} <= set(artifact["report"])


def test_trace_report_gate_record():
    ratchet = _load("ratchet")
    tr = _load("trace_report")
    artifact = tr.build_output("x/events.jsonl", tr.build_report(_good_events()))
    r = ratchet.trace_report_gate_record(artifact)
    assert r["ok"] and r["metric"] == "ratchet_trace_report_attribution"
    assert r["wall_s"] == pytest.approx(100.0)
    # inconsistent attribution fails the gate
    bad = tr.build_output(
        "x", tr.build_report(_good_events() + [
            _span("checkpoint_save", "main:checkpoint", 51.0, 3.0),
        ]),
    )
    r = ratchet.trace_report_gate_record(bad)
    assert not r["ok"] and "inconsistent" in r["error"]
    # a run with no flush boundaries means the recorder was dead
    silent = tr.build_output("x", tr.build_report([
        _span("first_step", "main:compile", 0.0, 1.0),
        _instant("end", "events", 10.0),
    ]))
    r = ratchet.trace_report_gate_record(silent)
    assert not r["ok"] and "flush-boundary" in r["error"]


def _fleet_session(run_dir, suffix="", scale=1.02, offset=5.0, late=0.4,
                   n_boundaries=3):
    """Write one recorder SESSION as two virtual processes: p0 on the
    reference clock, p1 on a rate-drifted + offset clock, arriving
    ``late`` seconds after p0 at every collective (the straggler)."""
    p0, p1 = [], []
    anchor = 0

    def boundary(name, kind, T, step=None):
        nonlocal anchor
        anchor += 1
        a0, a1 = T - late - 0.05, T - 0.05
        args = {"step": step} if step is not None else {}
        p0.append(_span(name, "main:collective", a0, T - a0, **args))
        p1.append(_span(name, "main:collective", scale * a1 + offset,
                        scale * (T - a1), **args))
        p0.append(_instant("clock_anchor", "fleet", T,
                           kind=kind, anchor=anchor))
        p1.append(_instant("clock_anchor", "fleet", scale * T + offset,
                           kind=kind, anchor=anchor))

    boundary("placement_decision", "placement", 1.0)
    for k in range(n_boundaries):
        boundary("failure_code_allgather", "flush_boundary",
                 10.0 + 5.0 * k, step=2 * (k + 1))
    p0.append(_span("flush_boundary", "main:flush", 2.0, 0.5, step=0))
    p1.append(_span("flush_boundary", "main:flush", scale * 2.0 + offset,
                    scale * 0.5, step=0))
    names = {0: f"events{suffix}.jsonl", 1: f"events_p1{suffix}.jsonl"}
    for pidx, events in ((0, p0), (1, p1)):
        with open(os.path.join(run_dir, names[pidx]), "w") as f:
            for e in sorted(events, key=lambda e: e["ts"]):
                f.write(json.dumps(e) + "\n")


def test_trace_report_fleet_cli_merges_two_virtual_processes(tmp_path):
    """The tier-1 fleet smoke: a 2-virtual-process run dir (two per-process
    events files on deliberately offset clocks, across TWO sessions) goes
    through the real ``--fleet`` CLI — sessions discovered and merged,
    anchors aligned to sub-tolerance residual, the injected straggler
    named, one pid per process in the merged Chrome trace."""
    tr = _load("trace_report")
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    _fleet_session(str(run_dir))
    _fleet_session(str(run_dir), suffix="_r2", offset=-3.0, late=0.2)
    # a torn tail on one file must not break the merge (SIGKILL session)
    with open(run_dir / "events_p1_r2.jsonl", "a") as f:
        f.write('{"half": ')
    out = tmp_path / "fleet.json"
    trace_out = tmp_path / "fleet_trace.json"
    rc = tr.main(["--fleet", str(run_dir), "--json", str(out),
                  "--trace", str(trace_out)])
    assert rc == 0
    artifact = json.load(open(out))
    assert artifact["schema"] == "fleet_report/v1" and artifact["ok"]
    assert sorted(artifact["sessions"]) == ["r1", "r2"]
    for label, rep in artifact["sessions"].items():
        cons = rep["consistency"]
        assert cons["ok"] and cons["n_processes"] == 2
        assert cons["max_residual_s"] <= tr.FLEET_RESIDUAL_TOL_S
        assert rep["straggler_ranking"][0]["process"] == 1
        assert all(r["straggler"] == 1 for r in rep["skew_table"])
        assert rep["files"] == {
            "0": "events.jsonl" if label == "r1" else "events_r2.jsonl",
            "1": "events_p1.jsonl" if label == "r1"
                 else "events_p1_r2.jsonl",
        }
    trace = json.load(open(trace_out))
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert pids == {0, 1}


def test_trace_report_fleet_cli_fails_on_recordless_process(tmp_path):
    """Review fix, CLI level: a discovered per-process file with zero
    complete records (dead-before-first-line process) must fail the merge
    rather than shrink the session to one process and exit 0."""
    tr = _load("trace_report")
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    _fleet_session(str(run_dir))
    (run_dir / "events_p1.jsonl").write_text('{"torn": ')  # nothing complete
    out = tmp_path / "fleet.json"
    rc = tr.main(["--fleet", str(run_dir), "--json", str(out)])
    assert rc == 1
    artifact = json.load(open(out))
    assert not artifact["ok"]
    rep = artifact["sessions"]["r1"]
    assert rep["consistency"]["n_processes"] == 2
    assert rep["processes"]["1"]["n_events"] == 0


def test_trace_report_flags_recorder_saturation():
    tr = _load("trace_report")
    events = _good_events() + [
        _instant("recorder_dropped", "events", 99.0, records=12),
    ]
    report = tr.build_report(events)
    joined = " ".join(a["flag"] for a in report["anomalies"])
    assert "ring saturated" in joined


def test_ratchet_fleet_and_ledger_in_default_gate_list():
    ratchet = _load("ratchet")
    assert ratchet.CONFIGS["fleet_report"]["kind"] == "fleet_report"
    assert ratchet.CONFIGS["perf_ledger"]["kind"] == "perf_ledger"
    # ...and the committed evidence artifacts they bind on exist and pass
    repo = os.path.dirname(SCRIPTS)
    with open(os.path.join(repo,
                           ratchet.CONFIGS["fleet_report"]["artifact"])) as f:
        fleet_artifact = json.load(f)
    assert ratchet.fleet_gate_record(fleet_artifact)["ok"]
    pl = _load("perf_ledger")
    records = pl.load_ledger(
        os.path.join(repo, ratchet.CONFIGS["perf_ledger"]["artifact"])
    )
    assert ratchet.ledger_gate_record(records)["ok"]


def test_no_stale_pycache_for_deleted_modules():
    """A __pycache__ .pyc whose source module no longer exists (e.g. the
    once-stray serve/__pycache__/registry.cpython-310.pyc) advertises a
    dead module name to grep/archaeology — untracked, so the git hygiene
    test above can't see it. Bytecode for LIVE modules is fine."""
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    pkg = os.path.join(repo, "simclr_pytorch_distributed_tpu")
    stale = []
    for dirpath, _, files in os.walk(pkg):
        if os.path.basename(dirpath) != "__pycache__":
            continue
        for f in files:
            if not f.endswith(".pyc"):
                continue
            module = f.split(".")[0] + ".py"
            if not os.path.exists(os.path.join(os.path.dirname(dirpath), module)):
                stale.append(os.path.relpath(os.path.join(dirpath, f), repo))
    assert not stale, f"stale bytecode for deleted modules: {stale}"
