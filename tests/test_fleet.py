"""Fleet-scale observability: clock-anchor alignment, the trace_report
--fleet merge (skew table + straggler attribution), the boundary-skew
piggyback on the failure-code allgather, and the longitudinal perf
ledger's regression scan.

Everything runs on synthetic offset clocks / fake allgathers / synthetic
ledger records — the machinery is pure by design, so tier-1 proves it
without a pod: two deliberately offset (and rate-drifted) virtual process
clocks must align to sub-tolerance residual, an injected per-process delay
must name the straggler, and an injected throughput regression must trip
the ledger gate while an unchanged trailing window passes.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from simclr_pytorch_distributed_tpu.utils import prom, tracing

pytestmark = pytest.mark.fleet

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


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------ clock anchors


def test_clock_anchor_event_schema_and_sequence():
    clk = FakeClock(10.0)
    rec = tracing.FlightRecorder(clock=clk)
    assert rec.clock_anchor("placement") == 1
    clk.advance(3.0)
    assert rec.clock_anchor("flush_boundary", step=4) == 2
    a, b = rec.snapshot()
    assert a["name"] == tracing.ANCHOR_EVENT and a["track"] == tracing.FLEET_TRACK
    assert a["args"] == {"kind": "placement", "anchor": 1}
    assert b["args"] == {"kind": "flush_boundary", "anchor": 2, "step": 4}
    assert b["ts"] == pytest.approx(3.0)


def test_module_level_clock_anchor_noop_without_recorder():
    tracing.uninstall()
    assert tracing.clock_anchor("placement") is None
    rec = tracing.FlightRecorder(clock=FakeClock())
    tracing.install(rec)
    try:
        assert tracing.clock_anchor("placement") == 1
    finally:
        tracing.uninstall()


# --------------------------------------------------- synthetic fleet runs


def _rec(lst, name, track, ts, dur=None, **args):
    e = {"name": name, "track": track, "ph": "i" if dur is None else "X",
         "ts": round(ts, 6)}
    if dur is not None:
        e["dur"] = round(dur, 6)
    if args:
        e["args"] = args
    lst.append(e)


def make_fleet(n_boundaries=4, late=0.55, scale=1.02, offset=5.0):
    """Two virtual processes observing the same run through different
    clocks: p0 is the reference; p1's clock reads ``scale*t + offset`` (a
    deliberate rate drift AND offset). p1 arrives ``late`` seconds after
    p0 at every collective; both stamp a clock anchor at the (shared)
    release instant T."""
    p0, p1 = [], []
    anchor = 0

    def boundary(name, kind, T, step=None):
        nonlocal anchor
        anchor += 1
        a0, a1 = T - late - 0.05, T - 0.05  # arrivals; release at T
        args = {} if step is None else {"step": step}
        _rec(p0, name, "main:collective", a0, T - a0, **args)
        _rec(p1, name, "main:collective", scale * a1 + offset,
             scale * (T - a1), **args)
        _rec(p0, "clock_anchor", "fleet", T, kind=kind, anchor=anchor)
        _rec(p1, "clock_anchor", "fleet", scale * T + offset,
             kind=kind, anchor=anchor)

    boundary("placement_decision", "placement", 1.0)
    for k in range(n_boundaries):
        boundary("failure_code_allgather", "flush_boundary", 10.0 + 5 * k,
                 step=2 * (k + 1))
    # a few main-thread phase spans so per-process attribution is real
    _rec(p0, "flush_boundary", "main:flush", 2.0, 0.5, step=0)
    _rec(p1, "flush_boundary", "main:flush", scale * 2.0 + offset,
         scale * 0.5, step=0)
    p0.sort(key=lambda e: e["ts"])
    p1.sort(key=lambda e: e["ts"])
    return {0: p0, 1: p1}


def test_fleet_merge_aligns_offset_clocks_and_names_straggler():
    """The acceptance-criteria core: two deliberately offset fake clocks
    align to sub-tolerance residual, and the injected per-process delay
    names process 1 the straggler at every boundary."""
    tr = _load("trace_report")
    report = tr.build_fleet_report(make_fleet())
    cons = report["consistency"]
    assert cons["ok"] and cons["n_processes"] == 2
    al = report["processes"]["1"]["alignment"]
    # exact affine clocks -> the fit recovers the inverse map exactly
    assert al["scale"] == pytest.approx(1 / 1.02, rel=1e-9)
    assert al["offset_s"] == pytest.approx(-5.0 / 1.02, abs=1e-4)
    assert al["residual_s"] < 1e-3 < tr.FLEET_RESIDUAL_TOL_S
    assert cons["max_residual_s"] < 1e-3
    # placement + 4 flush boundaries, each skewed by the injected 0.55 s
    assert len(report["skew_table"]) == 5
    for row in report["skew_table"]:
        assert row["skew_s"] == pytest.approx(0.55, abs=1e-3)
        assert row["straggler"] == 1
    ranking = report["straggler_ranking"]
    assert ranking[0]["process"] == 1 and ranking[0]["times_last"] == 5
    assert ranking[0]["mean_lateness_s"] == pytest.approx(0.55, abs=1e-3)
    # the rendered table names the straggler too
    assert "straggler=p1" in tr.render_fleet_table(report)


def test_fleet_merge_flags_missing_collective_member():
    tr = _load("trace_report")
    fleet = make_fleet()
    # p1 dies before the last boundary: its final collective span is gone
    dropped = [
        e for e in fleet[1]
        if not (e["track"] == "main:collective"
                and e.get("args", {}).get("step") == 8)
    ]
    report = tr.build_fleet_report({0: fleet[0], 1: dropped})
    cons = report["consistency"]
    assert cons["incomplete_boundaries"] == 1
    assert not cons["collective_match_ok"] and not cons["ok"]
    assert len(report["skew_table"]) == 4  # the whole boundaries remain


def test_fleet_merge_requires_two_anchors_per_process():
    tr = _load("trace_report")
    fleet = make_fleet()
    one_anchor = [
        e for e in fleet[1]
        if e["name"] != "clock_anchor"
        or e["args"]["anchor"] == 1
    ]
    report = tr.build_fleet_report({0: fleet[0], 1: one_anchor})
    assert report["processes"]["1"]["alignment"]["n_anchors"] == 1
    assert not report["consistency"]["aligned_ok"]
    assert not report["consistency"]["ok"]


def test_fleet_merge_fails_on_recordless_process():
    """Review fix: a process whose events file parsed to ZERO records (a
    SIGKILL before its first complete line) must fail the merge — not be
    silently dropped so the session reads as a consistent 1-process run."""
    tr = _load("trace_report")
    report = tr.build_fleet_report({0: make_fleet()[0], 1: []})
    cons = report["consistency"]
    assert cons["n_processes"] == 2 and not cons["ok"]
    assert not cons["aligned_ok"] and not cons["attribution_ok"]
    assert report["processes"]["1"]["n_events"] == 0
    assert report["processes"]["1"]["alignment"]["n_anchors"] == 0


def test_fleet_merge_single_process_is_trivially_consistent():
    tr = _load("trace_report")
    report = tr.build_fleet_report({0: make_fleet()[0]})
    cons = report["consistency"]
    assert cons["ok"] and cons["n_processes"] == 1
    assert report["skew_table"] == []


def test_fleet_chrome_trace_one_pid_per_process_nonnegative_ts():
    tr = _load("trace_report")
    fleet = make_fleet()
    report = tr.build_fleet_report(fleet)
    trace = tr.fleet_chrome_trace(fleet, report)
    assert set(trace) == {"traceEvents", "displayTimeUnit"}
    data = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    assert {e["pid"] for e in data} == {0, 1}
    assert min(e["ts"] for e in data) == 0  # shifted, never negative
    # aligned: both processes' anchor instants land at the same merged ts
    anchors = {}
    for e in data:
        if e["name"] == "clock_anchor":
            anchors.setdefault(e["args"]["anchor"], []).append(e["ts"])
    for seq, ts_list in anchors.items():
        assert len(ts_list) == 2
        assert abs(ts_list[0] - ts_list[1]) <= 2  # integer-us rounding


# -------------------------------------- the skew piggyback (telemetry side)


def test_failure_code_allgather_carries_wait_and_stamps_skew(monkeypatch):
    """The live half of the skew story: the EXISTING failure-code
    allgather widens to [code, prev_wait_ms] — no new collective — and the
    gathered waits become train_boundary_skew_seconds /
    train_collective_wait_seconds plus a boundary_skew event naming the
    straggler (the process that waited least = arrived last)."""
    import jax as jax_mod
    from jax.experimental import multihost_utils

    from simclr_pytorch_distributed_tpu.utils.telemetry import TelemetrySession

    payloads = []

    def fake_allgather(arr):
        arr = np.asarray(arr)
        payloads.append(arr.copy())
        # peer 1 reports a 400 ms previous wait; this host's prev rides in
        peer = np.asarray([0, 400], np.int32)
        return np.stack([arr, peer])

    monkeypatch.setattr(jax_mod, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather", fake_allgather)

    gauges = prom.TrainerGauges(clock=FakeClock())
    session = TelemetrySession(4, ("loss",), mode="sync", gauges=gauges)
    recorder = tracing.FlightRecorder(clock=FakeClock())
    tracing.install(recorder)
    try:
        session.check_failures_global(step_hint=2)
        # first boundary: this host has no previous wait yet (-1 sentinel)
        assert payloads[0].tolist() == [0, -1]
        out = gauges.collect()
        assert out["collective_wait_seconds"] >= 0.0
        assert "boundary_skew_seconds" not in out  # no full wait row yet
        session.check_failures_global(step_hint=4)
        # second boundary: the measured wait from boundary 1 piggybacks
        assert payloads[1][0] == 0 and payloads[1][1] >= 0
        out = gauges.collect()
        # waits were [~0 ms, 400 ms] -> skew ~0.4 s, straggler = this host
        assert out["boundary_skew_seconds"] == pytest.approx(0.4, abs=0.05)
        events = recorder.snapshot()
        skews = [e for e in events if e["name"] == "boundary_skew"]
        assert len(skews) == 1 and skews[0]["track"] == tracing.FLEET_TRACK
        assert skews[0]["args"]["straggler"] == 0
        anchors = [e for e in events if e["name"] == tracing.ANCHOR_EVENT]
        assert [a["args"]["anchor"] for a in anchors] == [1, 2]
        assert all(a["args"]["kind"] == "flush_boundary" for a in anchors)
        spans = [e for e in events if e["name"] == "failure_code_allgather"]
        assert len(spans) == 2 and all(
            s["track"] == "main:collective" for s in spans
        )
    finally:
        tracing.uninstall()
        session.close()


def test_single_process_boundary_publishes_zero_skew_and_anchor():
    from simclr_pytorch_distributed_tpu.utils.telemetry import TelemetrySession

    gauges = prom.TrainerGauges(clock=FakeClock())
    session = TelemetrySession(4, ("loss",), mode="sync", gauges=gauges)
    recorder = tracing.FlightRecorder(clock=FakeClock())
    tracing.install(recorder)
    try:
        session.check_failures_global(step_hint=2)
        out = gauges.collect()
        assert out["collective_wait_seconds"] == 0.0
        assert out["boundary_skew_seconds"] == 0.0
        (anchor,) = [
            e for e in recorder.snapshot()
            if e["name"] == tracing.ANCHOR_EVENT
        ]
        assert anchor["args"]["kind"] == "flush_boundary"
    finally:
        tracing.uninstall()
        session.close()


# --------------------------------------------- supervisor straggler finding


def test_straggler_finding_warn_only_surface():
    from simclr_pytorch_distributed_tpu.supervise import observe

    gauges = {
        "train_boundary_skew_seconds": 1.5,
        "train_collective_wait_seconds": 1.4,
        "train_step": 120.0,
    }
    finding = observe.straggler_finding(gauges, 1.0)
    assert finding == {"skew_s": 1.5, "bar_s": 1.0, "wait_s": 1.4,
                       "step": 120.0}
    assert observe.straggler_finding(gauges, 2.0) is None  # under the bar
    assert observe.straggler_finding(gauges, 0.0) is None  # disabled
    assert observe.straggler_finding(None, 1.0) is None    # dead sidecar
    assert observe.straggler_finding({}, 1.0) is None      # no skew gauge


def test_straggler_finding_carries_rebalance_context():
    """A PR-16 sidecar names the straggler and the fleet size; the finding
    must carry them plus the per-process share a restart_rebalanced
    decision shrinks (1/processes — EpochLoader's uniform blocks)."""
    from simclr_pytorch_distributed_tpu.supervise import observe

    gauges = {
        "train_boundary_skew_seconds": 1.5,
        "train_step": 120.0,
        "train_boundary_straggler": 1.0,
        "train_process_count": 4.0,
    }
    finding = observe.straggler_finding(gauges, 1.0)
    assert finding["straggler"] == 1
    assert finding["processes"] == 4 and finding["share"] == 0.25


def test_straggler_finding_identity_gauges_missing_or_single_process():
    """Against an older sidecar (no identity gauges) the finding still
    fires but carries no identity — enough to warn, not to mitigate; a
    single-process fleet's -1 sentinel is likewise not an identity."""
    from simclr_pytorch_distributed_tpu.supervise import observe

    old = {"train_boundary_skew_seconds": 1.5, "train_step": 3.0}
    finding = observe.straggler_finding(old, 1.0)
    assert finding is not None
    assert "straggler" not in finding and "processes" not in finding

    single = dict(old, train_boundary_straggler=-1.0,
                  train_process_count=1.0)
    finding = observe.straggler_finding(single, 1.0)
    assert "straggler" not in finding  # -1 = nobody was waited on
    assert finding["processes"] == 1 and finding["share"] == 1.0


def test_supervisor_records_straggler_finding_once_per_step(tmp_path):
    from simclr_pytorch_distributed_tpu.supervise import supervisor as sup

    cfg = sup.SuperviseConfig(
        command=["true"], workdir=str(tmp_path), metrics_port=9,
        straggler_skew_secs=1.0,
    )

    class FakeScraper:
        def __init__(self):
            self.gauges = {
                "train_last_boundary_age_seconds": 0.5,
                "train_boundary_skew_seconds": 2.0,
                "train_step": 40.0,
            }

        def scrape(self):
            return dict(self.gauges)

    class DoneChild:
        pid = 1234

        def __init__(self):
            self.polls = 0

        def poll(self):
            # two observation loops, then exit 0
            self.polls += 1
            return 0 if self.polls >= 3 else None

    scraper = FakeScraper()
    s = sup.Supervisor(cfg, sleep=lambda dt: None, scraper=scraper)
    s.child = DoneChild()
    rc, stalled, dumps, alarms = s._watch_child()
    assert rc == 0 and not stalled
    findings = [
        e for e in s.recorder.snapshot() if e["name"] == "straggler_finding"
    ]
    # same step scraped on both polls: recorded ONCE, warn-only (no kill)
    assert len(findings) == 1
    assert findings[0]["args"]["skew_s"] == 2.0
    assert findings[0]["args"]["step"] == 40.0
    s.recorder.close()


# ------------------------------------------------- health_report sessions


def test_health_report_reads_rotated_sessions(tmp_path):
    """Satellite: a resumed run's health timeline spans events.jsonl +
    events_r2.jsonl (+...); reading only the first file silently truncated
    it at the first preemption."""
    import scripts.health_report as hr

    keys = dict.fromkeys(hr.REQUIRED_HEALTH_KEYS, 1.0)

    def window(step):
        return {"name": "health_window", "track": "health", "ph": "i",
                "ts": 0.1 * step, "args": dict(keys, step=step)}

    with open(tmp_path / "events.jsonl", "w") as f:
        for s in (2, 4):
            f.write(json.dumps(window(s)) + "\n")
    with open(tmp_path / "events_r2.jsonl", "w") as f:
        for s in (6, 8):
            f.write(json.dumps(window(s)) + "\n")
        f.write('{"torn": ')  # SIGKILL mid-line: must not crash the reader
    events = hr.load_events(str(tmp_path / "events.jsonl"))
    report = hr.build_report(events)
    assert report["consistency"]["n_windows"] == 4
    assert report["consistency"]["ok"]
    assert [w["step"] for w in report["timeline"]] == [2, 4, 6, 8]
    # an EXPLICIT rotated file selects exactly that session — asking for
    # one session must not be silently overridden with the whole family
    r2 = str(tmp_path / "events_r2.jsonl")
    assert hr.session_paths(r2) == [r2]
    solo = hr.build_report(hr.load_events(r2))
    assert [w["step"] for w in solo["timeline"]] == [6, 8]
    # ...and the artifact provenance records the files ACTUALLY read
    art = hr.build_output(
        str(tmp_path / "events.jsonl"), report, "cpu",
        session_files=hr.session_paths(str(tmp_path / "events.jsonl")),
    )
    assert art["session_files"] == ["events.jsonl", "events_r2.jsonl"]


# ------------------------------------------------------------- perf ledger


def _bench_record(value=4000.0, device_kind="cpu", chips=1,
                  clock_suspect=False, config="simclr rn50 bsz256"):
    return {
        "metric": "pretrain_imgs_per_sec_per_chip",
        "value": value,
        "vs_baseline": 1.0,
        "detail": {
            "global_batch": 256, "chips": chips,
            "device_kind": device_kind, "step_ms": 63.0,
            "clock_suspect": clock_suspect, "config": config,
        },
    }


def test_ledger_record_schema_and_fingerprint_identity():
    pl = _load("perf_ledger")
    rec = pl.record_from_bench(
        _bench_record(), "abc1234", 1722.0,
        phase_shares={"flush": 0.01, "steady_state": 0.9},
    )
    assert rec["schema"] == pl.SCHEMA
    assert not pl.schema_errors([rec])
    assert rec["imgs_per_sec_per_chip"] == 4000.0
    assert rec["git_rev"] == "abc1234" and rec["stage"] == "pretrain"
    assert rec["phase_shares"]["steady_state"] == 0.9
    # fingerprint: stable for the same workload, different across devices
    again = pl.record_from_bench(_bench_record(3900.0), "def", 1723.0)
    other = pl.record_from_bench(
        _bench_record(device_kind="TPU v5 lite"), "def", 1723.0
    )
    assert rec["fingerprint"] == again["fingerprint"]
    assert rec["fingerprint"] != other["fingerprint"]


def _ledger(values, suspects=None, shares=None):
    pl = _load("perf_ledger")
    suspects = suspects or [False] * len(values)
    out = []
    for i, (v, sus) in enumerate(zip(values, suspects)):
        rec = pl.record_from_bench(
            _bench_record(v, clock_suspect=sus), f"rev{i}", 1000.0 + i,
            phase_shares=(shares[i] if shares else None),
        )
        out.append(rec)
    return pl, out


def test_ledger_regression_and_no_regression_pair():
    """The acceptance-criteria pair: an unchanged trailing window passes;
    an injected regression is flagged — through the pure gate record."""
    ratchet = _load("ratchet")
    # unchanged: latest within noise of the trailing median
    pl, steady = _ledger([4000.0, 4010.0, 3995.0, 4005.0])
    verdicts = pl.detect_regression(steady)
    (v,) = verdicts.values()
    assert v["status"] == "ok" and v["ratio"] == pytest.approx(1.0, abs=0.01)
    rec = ratchet.ledger_gate_record(steady)
    assert rec["ok"] and rec["metric"] == "ratchet_perf_ledger"
    # injected regression: latest at 90% of the window median
    shares = [
        {"flush": 0.01, "steady_state": 0.95},
        {"flush": 0.01, "steady_state": 0.95},
        {"flush": 0.01, "steady_state": 0.95},
        {"flush": 0.12, "steady_state": 0.84},  # flush absorbed the time
    ]
    pl, regressed = _ledger([4000.0, 4010.0, 3995.0, 3600.0], shares=shares)
    verdicts = pl.detect_regression(regressed)
    (v,) = verdicts.values()
    assert v["status"] == "regression"
    assert v["ratio"] == pytest.approx(3600.0 / 4000.0, abs=0.01)
    assert v["latest_rev"] == "rev3"
    # ...and the drift is attributed to a PHASE, not just a revision
    assert v["phase_suspect"]["phase"] == "flush"
    rec = ratchet.ledger_gate_record(regressed)
    assert not rec["ok"] and "regression" in rec["error"]
    assert "rev3" in rec["error"]


def test_ledger_excludes_clock_suspect_runs_both_sides():
    pl, records = _ledger(
        [4000.0, 4010.0, 3995.0, 9000.0, 3990.0],
        suspects=[False, False, False, True, False],
    )
    (v,) = pl.detect_regression(records).values()
    # the 9000 glitch neither sets the baseline nor becomes the subject
    assert v["status"] == "ok" and v["window"] == 3
    assert v["baseline_median"] == pytest.approx(4000.0)
    # a glitched LATEST run cannot mask anything either: the last clean
    # record is judged instead
    pl2, records2 = _ledger(
        [4000.0, 4010.0, 3600.0, 9000.0],
        suspects=[False, False, False, True],
    )
    (v2,) = pl2.detect_regression(records2).values()
    assert v2["status"] == "regression" and v2["latest_rev"] == "rev2"


def test_ledger_short_window_pass_skips_with_reason():
    ratchet = _load("ratchet")
    pl, records = _ledger([4000.0, 3000.0])  # one trailing record only
    (v,) = pl.detect_regression(records).values()
    assert v["status"] == "skipped" and "window" in v["reason"]
    rec = ratchet.ledger_gate_record(records)
    assert rec["ok"] and rec["skipped"]
    # empty and schema-broken ledgers fail loudly
    assert not ratchet.ledger_gate_record([])["ok"]
    bad = ratchet.ledger_gate_record([{"schema": "bogus"}])
    assert not bad["ok"] and "schema" in bad["error"]


def test_ledger_check_cli_reports_schema_error_not_keyerror(tmp_path):
    """Review fix: a malformed ledger line (missing pinned keys) must
    surface as a schema error through the check CLI, not crash
    detect_regression with a KeyError."""
    pl = _load("perf_ledger")
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text('{"schema": "perf_ledger/v1"}\n')
    out = tmp_path / "check.json"
    rc = pl.main(["check", "--ledger", str(ledger), "--json", str(out)])
    assert rc == 1
    artifact = json.load(open(out))
    assert not artifact["ok"]
    assert artifact["schema_errors"] and artifact["verdicts"] == {}


def test_ledger_corrupt_complete_line_fails_gate_torn_tail_tolerated(tmp_path):
    """Review fix: the ledger loader tolerates only a torn FINAL line (an
    append racing the reader); a complete-but-corrupt line must surface as
    a schema error — a silently vanished newest record would make the
    previous one 'latest' and blind the regression scan."""
    pl = _load("perf_ledger")
    ratchet = _load("ratchet")
    good = pl.record_from_bench(_bench_record(), "rev0", 1000.0)
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text(
        json.dumps(good) + "\n"
        + "<<<<<<< conflict marker\n"       # complete corrupt line
        + json.dumps(good) + "\n"
        + '{"schema": "perf_ledger/v1", '    # torn tail: tolerated
    )
    records = pl.load_ledger(str(ledger))
    assert len(records) == 3  # the torn tail is not a record
    errors = pl.schema_errors(records)
    assert len(errors) == 1 and "unparseable" in errors[0]
    rec = ratchet.ledger_gate_record(records)
    assert not rec["ok"] and "schema" in rec["error"]
    # without the corrupt line the same ledger is clean
    ledger.write_text(json.dumps(good) + "\n" + json.dumps(good) + "\n")
    assert ratchet.ledger_gate_record(pl.load_ledger(str(ledger)))["ok"]


def test_ledger_append_and_check_cli_roundtrip(tmp_path):
    pl = _load("perf_ledger")
    bench_log = tmp_path / "bench.log"
    bench_log.write_text(
        "warmup noise\n" + json.dumps(_bench_record(4000.0)) + "\n"
    )
    ledger = tmp_path / "ledger.jsonl"
    for _ in range(3):
        assert pl.main(["append", "--bench-json", str(bench_log),
                        "--ledger", str(ledger)]) == 0
    out = tmp_path / "check.json"
    assert pl.main(["check", "--ledger", str(ledger),
                    "--json", str(out)]) == 0
    artifact = json.load(open(out))
    assert artifact["schema"] == "perf_ledger_check/v1"
    assert artifact["n_records"] == 3 and artifact["ok"]
    (v,) = artifact["verdicts"].values()
    assert v["status"] == "ok" and v["window"] == 2
    # all three appends share the workload fingerprint and carry a git rev
    records = pl.load_ledger(str(ledger))
    assert len({r["fingerprint"] for r in records}) == 1
    assert all(r["git_rev"] for r in records)


def test_ledger_append_from_bench_attaches_phase_shares(tmp_path):
    pl = _load("perf_ledger")
    tr = _load("trace_report")
    phases = tmp_path / "trace_report.json"
    events = [
        {"name": "first_step", "track": "main:compile", "ph": "X",
         "ts": 0.0, "dur": 10.0},
        {"name": "flush_boundary", "track": "main:flush", "ph": "X",
         "ts": 50.0, "dur": 2.0},
        {"name": "end", "track": "events", "ph": "i", "ts": 100.0},
    ]
    with open(phases, "w") as f:
        json.dump(tr.build_output("x", tr.build_report(events)), f)
    ledger = tmp_path / "ledger.jsonl"
    rec = pl.append_from_bench(
        str(ledger), _bench_record(), phases_path=str(phases), note="n1"
    )
    assert rec["phase_shares"]["compile"] == pytest.approx(0.10)
    assert rec["phase_shares"]["steady_state"] == pytest.approx(0.88)
    assert rec["note"] == "n1"
    (loaded,) = pl.load_ledger(str(ledger))
    assert loaded == json.loads(json.dumps(rec))  # round-trips losslessly


# ------------------------------------------------------- fleet ratchet gate


def test_fleet_gate_record_pass_and_failures():
    ratchet = _load("ratchet")
    tr = _load("trace_report")
    fleet = make_fleet()
    good = tr.build_fleet_output(
        "run", {"r1": tr.build_fleet_report(fleet)}
    )
    rec = ratchet.fleet_gate_record(good)
    assert rec["ok"] and rec["metric"] == "ratchet_fleet_report"
    assert rec["stragglers"] == {"r1": 1}
    assert rec["max_residual_s"] <= tr.FLEET_RESIDUAL_TOL_S
    # a single-process-only artifact proves nothing about alignment
    solo = tr.build_fleet_output(
        "run", {"r1": tr.build_fleet_report({0: fleet[0]})}
    )
    rec = ratchet.fleet_gate_record(solo)
    assert not rec["ok"] and "multi-process" in rec["error"]
    # an inconsistent merge fails
    broken = [
        e for e in fleet[1]
        if e["name"] != "clock_anchor" or e["args"]["anchor"] == 1
    ]
    bad = tr.build_fleet_output(
        "run", {"r1": tr.build_fleet_report({0: fleet[0], 1: broken})}
    )
    rec = ratchet.fleet_gate_record(bad)
    assert not rec["ok"] and "inconsistent" in rec["error"]
    # empty / wrong-schema artifacts fail
    assert not ratchet.fleet_gate_record({"schema": "fleet_report/v1",
                                          "sessions": {}})["ok"]
    assert not ratchet.fleet_gate_record({"schema": "nope"})["ok"]
