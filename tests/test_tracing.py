"""Observability layer tests: flight recorder, stall watchdog, Prometheus
exposition, and the tier-1 recorder-overhead proof.

Everything timing-shaped runs on fake clocks (the watchdog's ``check()`` is
the testable core — the background thread only calls it on a cadence), and
the "zero added device transfers" claim is MECHANICAL: a real driver epoch
runs with the recorder on while the metric ring's ``device_get`` and the
device store's ``index_put`` count every transfer — the counts must equal
the PR-4/PR-5 proven contract (one ring D2H per window, one index upload
per epoch) exactly, recorder or no recorder.
"""

import json
import logging
import os
import time
import urllib.request

import numpy as np
import pytest

from simclr_pytorch_distributed_tpu.utils import prom, tracing

pytestmark = pytest.mark.obs

SIZE = 8


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------- recorder


def test_recorder_jsonl_roundtrip_and_snapshot(tmp_path):
    clk = FakeClock(100.0)
    path = str(tmp_path / "events.jsonl")
    rec = tracing.FlightRecorder(path, clock=clk)
    with rec.span("phase_a", track="main:flush", step=3):
        clk.advance(0.5)
    clk.advance(0.25)
    rec.event("nan_rollback", track="main:guard", epoch=2)
    rec.close()

    lines = [json.loads(x) for x in open(path).read().splitlines()]
    assert [e["name"] for e in lines] == ["phase_a", "nan_rollback"]
    span = lines[0]
    assert span["ph"] == "X" and span["track"] == "main:flush"
    assert span["ts"] == pytest.approx(0.0) and span["dur"] == pytest.approx(0.5)
    assert span["args"] == {"step": 3}
    ev = lines[1]
    assert ev["ph"] == "i" and ev["ts"] == pytest.approx(0.75)
    # snapshot is the same records (the watchdog dump source)
    snap = rec.snapshot()
    assert [e["name"] for e in snap] == ["phase_a", "nan_rollback"]
    assert rec.snapshot(last=1)[0]["name"] == "nan_rollback"


def test_recorder_record_span_explicit_clock_domain():
    clk = FakeClock(10.0)
    rec = tracing.FlightRecorder(clock=clk)
    start = rec.now()
    clk.advance(2.0)
    rec.record_span("request", "serve:request", start, rec.now(), n=4)
    (span,) = rec.snapshot()
    assert span["ts"] == pytest.approx(0.0) and span["dur"] == pytest.approx(2.0)


def test_chrome_trace_export_schema(tmp_path):
    """Schema pin: Chrome trace-event JSON with integer microsecond
    ts/dur, thread_name metadata per track, and monotone non-overlapping
    spans within each main:* track."""
    clk = FakeClock()
    trace_path = str(tmp_path / "trace.json")
    rec = tracing.FlightRecorder(clock=clk, trace_path=trace_path)
    for _ in range(3):  # sequential spans on one track
        with rec.span("flush_boundary", track="main:flush"):
            clk.advance(0.01)
        clk.advance(0.05)
    with rec.span("first_step", track="main:compile"):
        clk.advance(1.0)
    rec.event("cache_hits", track="serve:cache", rows=2)
    rec.close()

    trace = json.load(open(trace_path))
    assert set(trace) == {"traceEvents", "displayTimeUnit"}
    events = trace["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {
        "main:flush", "main:compile", "serve:cache"
    }
    by_track_tid = {m["args"]["name"]: m["tid"] for m in metas}
    spans = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert len(spans) == 4 and len(instants) == 1
    for e in spans:
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] == 0
    # per-main-track monotone non-overlap (the attribution invariant)
    flush = sorted(
        (e for e in spans if e["tid"] == by_track_tid["main:flush"]),
        key=lambda e: e["ts"],
    )
    assert len(flush) == 3
    for a, b in zip(flush, flush[1:]):
        assert b["ts"] >= a["ts"] + a["dur"]


def test_recorder_ring_bound_drops_oldest_keeps_jsonl(tmp_path):
    path = str(tmp_path / "events.jsonl")
    rec = tracing.FlightRecorder(path, clock=FakeClock(), max_events=4)
    for i in range(10):
        rec.event(f"e{i}")
    assert [e["name"] for e in rec.snapshot()] == ["e6", "e7", "e8", "e9"]
    assert rec.dropped == 6
    rec.close()
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    # disk keeps all 10, plus the close-time saturation marker so a
    # post-mortem (trace_report flags it) knows the ring views truncated
    assert len(lines) == 11
    assert lines[-1]["name"] == "recorder_dropped"
    assert lines[-1]["args"]["records"] == 6


def test_recorder_close_without_drops_stays_silent(tmp_path):
    path = str(tmp_path / "events.jsonl")
    rec = tracing.FlightRecorder(path, clock=FakeClock())
    rec.event("only")
    rec.close()
    names = [json.loads(x)["name"] for x in open(path).read().splitlines()]
    assert names == ["only"]


# ------------------------------------------- shared torn-tolerant loader


def test_parse_jsonl_tolerates_torn_tail_and_corrupt_lines(tmp_path):
    """The satellite round-trip: the one shared loader (trace_report,
    health_report, the supervisor's watcher, the perf ledger) must survive
    the half-written final line a SIGKILL leaves behind AND a corrupt
    middle line, consuming only complete lines."""
    good = [{"name": "a", "ts": 1.0}, {"name": "b", "ts": 2.0}]
    text = (
        json.dumps(good[0]) + "\n"
        + "{not json}\n"          # complete but corrupt: skipped
        + json.dumps(good[1]) + "\n"
        + '{"name": "torn", "ts'  # no newline: the SIGKILL tail
    )
    records, consumed = tracing.parse_jsonl(text)
    assert records == good
    assert consumed == len(text) - len('{"name": "torn", "ts')
    path = tmp_path / "events.jsonl"
    path.write_text(text)
    assert tracing.load_events_jsonl(str(path)) == good
    # incremental-tail contract: appending the rest of the torn line makes
    # it parse from the recorded offset (the RunDirWatcher pattern)
    with open(path, "a") as f:
        f.write('": 3.0}\n')
    tail, _ = tracing.parse_jsonl(path.read_text()[consumed:])
    assert tail == [{"name": "torn", "ts": 3.0}]


def test_session_files_for_orders_rotations(tmp_path):
    for name in ("events.jsonl", "events_r2.jsonl", "events_r4.jsonl",
                 "events_p1.jsonl", "events_p1_r2.jsonl"):
        (tmp_path / name).write_text("")
    files = tracing.session_files_for(str(tmp_path / "events.jsonl"))
    # stops at the first missing rotation (r3): r4 is another process's
    # numbering error, not a later session of this run
    assert [os.path.basename(p) for p in files] == [
        "events.jsonl", "events_r2.jsonl"
    ]
    files = tracing.session_files_for(str(tmp_path / "events_p1.jsonl"))
    assert [os.path.basename(p) for p in files] == [
        "events_p1.jsonl", "events_p1_r2.jsonl"
    ]
    # unknown names degrade to themselves
    other = str(tmp_path / "whatever.jsonl")
    assert tracing.session_files_for(other) == [other]


def test_discover_fleet_sessions_groups_processes_and_sessions(tmp_path):
    for name in ("events.jsonl", "events_p1.jsonl", "events_r2.jsonl",
                 "events_p1_r2.jsonl", "trace.json", "stall_dump_1.txt"):
        (tmp_path / name).write_text("")
    sessions = tracing.discover_fleet_sessions(str(tmp_path))
    assert list(sessions) == ["r1", "r2"]
    assert {p: os.path.basename(f) for p, f in sessions["r1"].items()} == {
        0: "events.jsonl", 1: "events_p1.jsonl"
    }
    assert {p: os.path.basename(f) for p, f in sessions["r2"].items()} == {
        0: "events_r2.jsonl", 1: "events_p1_r2.jsonl"
    }


def test_module_level_helpers_noop_without_install(tmp_path):
    tracing.uninstall()
    with tracing.span("x", track="main:flush"):
        pass
    tracing.event("y")
    tracing.record_span("z", "t", 0.0, 1.0)  # all silently dropped
    rec = tracing.FlightRecorder(clock=FakeClock())
    tracing.install(rec)
    try:
        with tracing.span("x", track="main:flush"):
            pass
        tracing.event("y")
    finally:
        tracing.uninstall()
    assert [e["name"] for e in rec.snapshot()] == ["x", "y"]


# ------------------------------------------------------------- watchdog


def test_watchdog_fires_on_stuck_boundary_and_dumps_artifacts(tmp_path):
    clk = FakeClock()
    rec = tracing.FlightRecorder(clock=clk)
    rec.event("last_good_boundary", track="main:flush", step=40)
    wd = tracing.StallWatchdog(
        10.0, str(tmp_path), clock=clk, recorder=rec, start=False,
        name="train",
    )
    wd.beat()
    clk.advance(5.0)
    assert not wd.check()  # within deadline: silent
    clk.advance(6.0)
    assert wd.check()  # 11s > 10s: fires
    txt = tmp_path / "stall_dump_1.txt"
    js = tmp_path / "stall_dump_1.json"
    assert txt.exists() and js.exists()
    body = txt.read_text()
    # faulthandler wrote real stacks: this very test frame is in them
    assert "STALL" in body and "test_tracing" in body
    dump = json.loads(js.read_text())
    assert dump["age_s"] == pytest.approx(11.0)
    assert any(e["name"] == "last_good_boundary" for e in dump["events"])
    # one dump per stall: no re-fire until a beat re-arms
    clk.advance(100.0)
    assert not wd.check()
    wd.beat()
    clk.advance(11.0)
    assert wd.check()
    assert (tmp_path / "stall_dump_2.txt").exists()


def test_watchdog_silent_on_healthy_run(tmp_path):
    clk = FakeClock()
    wd = tracing.StallWatchdog(10.0, str(tmp_path), clock=clk, start=False)
    for _ in range(20):
        clk.advance(5.0)
        wd.beat()
        assert not wd.check()
    assert list(tmp_path.iterdir()) == []


def test_watchdog_disarm_suppresses_then_arm_restores(tmp_path):
    clk = FakeClock()
    wd = tracing.StallWatchdog(10.0, str(tmp_path), clock=clk, start=False)
    wd.disarm()
    clk.advance(100.0)
    assert not wd.check()  # disarmed silence is expected (idle serve)
    wd.arm()
    assert not wd.check()  # arm() beats: full deadline from here
    clk.advance(11.0)
    assert wd.check()


def test_watchdog_rejects_nonpositive_deadline(tmp_path):
    with pytest.raises(ValueError):
        tracing.StallWatchdog(0.0, str(tmp_path), start=False)


# ------------------------------------------------- logging dedup satellite


def test_setup_logging_dedups_file_handlers(tmp_path):
    """Regression (satellite): repeated setup_logging calls against the
    same work_dir must not stack duplicate ``log-ing`` FileHandlers — each
    stacked handler wrote every line once more (resume loops, tests)."""
    from simclr_pytorch_distributed_tpu.utils.logging_utils import setup_logging

    root = logging.getLogger()
    before = list(root.handlers)
    try:
        for _ in range(3):
            setup_logging(str(tmp_path), is_main=True)
        target = os.path.abspath(os.path.join(str(tmp_path), "log-ing"))
        mine = [
            h for h in root.handlers
            if isinstance(h, logging.FileHandler)
            and getattr(h, "baseFilename", None) == target
        ]
        assert len(mine) == 1
        logging.getLogger().info("exactly-once-line")
        mine[0].flush()
        text = open(target).read()
        assert text.count("exactly-once-line") == 1
    finally:
        for h in list(root.handlers):
            if h not in before:
                root.removeHandler(h)
                h.close()


# ------------------------------------------------------------------ prom


def test_render_prometheus_format_and_escaping():
    text = prom.render_prometheus([
        ("train_step", None, 42),
        ("lat_bucket", {"bucket": "8", "le": "+Inf"}, 3),
        ("weird", {"l": 'a"b\nc'}, 1.5),
    ])
    lines = text.splitlines()
    assert lines[0] == "train_step 42"
    assert lines[1] == 'lat_bucket{bucket="8",le="+Inf"} 3'
    assert "\\n" in lines[2] and '\\"' in lines[2]
    assert text.endswith("\n")


def test_latency_histogram_quantiles_and_samples():
    h = prom.LatencyHistogram(bounds_ms=(1, 10, 100, 1000))
    for ms in (5, 5, 5, 5, 5, 5, 5, 5, 5, 50):  # 9 fast + 1 slow
        h.observe(8, ms)
    s = h.summary()["8"]
    assert s["count"] == 10
    assert 1 < s["p50_ms"] <= 10
    assert 10 < s["p95_ms"] <= 100  # the slow one pulls the tail bucket
    assert s["p50_ms"] <= s["p95_ms"] <= s["p99_ms"]
    # overflow clamps to the top bound instead of inventing a number
    h.observe("big", 99999)
    assert h.quantile("big", 0.5) == 1000
    samples = h.samples("req_ms")
    names = {n for n, _, _ in samples}
    assert names == {"req_ms_bucket", "req_ms_sum", "req_ms_count"}
    inf_8 = [v for n, lab, v in samples
             if n == "req_ms_bucket" and lab == {"bucket": "8", "le": "+Inf"}]
    assert inf_8 == [10]
    # cumulative within one key: counts never decrease along the bounds
    buckets_8 = [v for n, lab, v in samples
                 if n == "req_ms_bucket" and lab.get("bucket") == "8"]
    assert buckets_8 == sorted(buckets_8)


def test_latency_histogram_rejects_bad_bounds():
    with pytest.raises(ValueError):
        prom.LatencyHistogram(bounds_ms=(10, 5))


def test_trainer_gauges_liveness_age():
    clk = FakeClock()
    g = prom.TrainerGauges(clock=clk)
    assert g.collect()["last_boundary_age_seconds"] == -1.0  # no beat yet
    g.beat(120)
    g.set(epoch=3, inflight_windows=1)
    clk.advance(7.5)
    g.register("checkpoint_pending_saves", lambda: 2)
    out = g.collect()
    assert out["step"] == 120 and out["epoch"] == 3
    assert out["last_boundary_age_seconds"] == pytest.approx(7.5)
    assert out["checkpoint_pending_saves"] == 2
    g.register("broken", lambda: 1 / 0)
    assert g.collect()["broken"] == -1.0  # a scrape never raises
    assert "train_step 120" in g.prometheus_text()


def test_trainer_gauges_supervisor_surface():
    """The supervisor-facing gauges (docs/RESILIENCE.md): start_time_seconds
    is stamped from the injectable WALL clock at construction (uptime
    without /proc), and exit_code is a terminal gauge — absent until the
    driver's exit path stamps it (RunObservability.close), then exposed so
    the last scrape classifies the exit."""
    g = prom.TrainerGauges(clock=FakeClock(), wall_clock=lambda: 1722.25)
    out = g.collect()
    assert out["start_time_seconds"] == 1722.25
    assert "exit_code" not in out  # terminal: absent while running
    g.set_exit_code(75)
    assert g.collect()["exit_code"] == 75.0
    text = g.prometheus_text()
    assert "train_start_time_seconds 1722.25" in text
    assert "train_exit_code 75" in text


def test_metrics_sidecar_http_endpoint():
    g = prom.TrainerGauges(clock=FakeClock())
    g.beat(7)
    server = prom.start_metrics_server(0, g.prometheus_text, host="127.0.0.1")
    try:
        host, port = server.server_address[:2]
        with urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=10
        ) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
        assert "train_step 7" in body
        assert "train_last_boundary_age_seconds" in body
        with urllib.request.urlopen(
            f"http://{host}:{port}/healthz", timeout=10
        ) as r:
            assert json.loads(r.read()) == {"status": "ok"}
    finally:
        server.shutdown()
        server.server_close()


# ------------------------------------ the recorder-overhead proof (tier-1)


def test_recorder_adds_no_device_transfers_in_driver_hot_loop(
    tmp_path, monkeypatch
):
    """The acceptance-criteria proof, mechanical: one REAL supcon epoch
    under device placement with the flight recorder ON, every ring D2H
    counted through the MetricRing's injectable ``device_get`` and every
    index upload through the DeviceStore's ``index_put``. The counts must
    equal the PR-4/PR-5 contract exactly — 3 ring transfers (windows
    2+2+1 of a 5-step epoch at print_freq 2) and 1 index upload (one
    epoch) — so the recorder added ZERO device transfers between flush
    boundaries, while events.jsonl proves it was live the whole time."""
    import jax as _jax

    from simclr_pytorch_distributed_tpu import config as config_lib
    from simclr_pytorch_distributed_tpu.data import cifar as cifar_lib
    from simclr_pytorch_distributed_tpu.data import device_store
    from simclr_pytorch_distributed_tpu.parallel import mesh as mesh_lib
    from simclr_pytorch_distributed_tpu.train import supcon as supcon_driver
    from simclr_pytorch_distributed_tpu.utils.telemetry import TelemetrySession

    orig_synth = cifar_lib.synthetic_dataset
    monkeypatch.setattr(
        cifar_lib, "synthetic_dataset",
        lambda n=2048, num_classes=10, seed=0, size=32: orig_synth(
            n=200, num_classes=num_classes, seed=seed, size=SIZE
        ),
    )
    monkeypatch.setattr(
        supcon_driver, "create_mesh",
        lambda devices=None, **kw: mesh_lib.create_mesh(
            devices=_jax.devices()[:1] if devices is None else devices, **kw
        ),
    )

    counts = {"ring": 0, "index": 0}

    class CountingSession(TelemetrySession):
        def __init__(self, window, keys, mode="async", **kw):
            def counting_get(x):
                counts["ring"] += 1
                return _jax.device_get(x)

            super().__init__(
                window, keys, mode, device_get=counting_get, **kw
            )

    real_store = device_store.DeviceStore

    class CountingStore(real_store):
        def __init__(self, loader, mesh, **kw):
            super().__init__(loader, mesh, **kw)
            inner = self._index_put

            def counting_put(idx):
                counts["index"] += 1
                return inner(idx)

            self._index_put = counting_put

    monkeypatch.setattr(supcon_driver, "TelemetrySession", CountingSession)
    monkeypatch.setattr(device_store, "DeviceStore", CountingStore)

    cfg = config_lib.SupConConfig(
        model="resnet10", dataset="synthetic", batch_size=32, epochs=1,
        learning_rate=0.05, cosine=True, save_freq=5, print_freq=2,
        size=SIZE, workdir=str(tmp_path), seed=0, method="SimCLR",
        telemetry="sync", data_placement="device", flight_recorder="on",
    )
    cfg = config_lib.finalize_supcon(cfg)
    supcon_driver.run(cfg)

    # the mechanical bound: exactly the pre-recorder transfer contract
    assert counts == {"ring": 3, "index": 1}

    # ...and the recorder really was on through the whole loop
    events_path = os.path.join(cfg.save_folder, "events.jsonl")
    events = [json.loads(x) for x in open(events_path).read().splitlines()]
    boundaries = [e for e in events if e["name"] == "flush_boundary"]
    # 3 real windows (2+2+1) + the epoch-tail boundary finish_epoch submits
    # with ZERO pending steps — a span records (the recorder saw it) but no
    # transfer happened (the ring count above stayed 3)
    assert len([b for b in boundaries if b["args"]["steps"] > 0]) == 3
    assert all(b["args"]["steps"] == 0 for b in boundaries[3:])
    # what the hot loop accumulated rides the boundary's span (PR 25): the
    # loop itself still records nothing (tests/test_step_scopes.py)
    assert all(("dispatch_s" in b["args"]) == (b["args"]["steps"] > 0)
               for b in boundaries)
    assert any(e["name"] == "drain_wait" and e["track"] == "main:flush"
               for e in events)
    assert any(e["name"] == "first_step" for e in events)
    assert any(e["name"] == "epoch_gather" for e in events)
    assert any(e["name"] == "epoch" for e in events)
    assert any(e["name"] == "checkpoint_save" for e in events)
    assert os.path.exists(os.path.join(cfg.save_folder, "trace.json"))

    # ...and the FLEET instrumentation (clock anchors at the placement
    # agreement + every flush-boundary failure observation) was live for
    # the whole run while the transfer count above stayed at the PR-4/PR-5
    # contract: the anchors are host-only stamps, zero device cost
    anchors = [e for e in events if e["name"] == tracing.ANCHOR_EVENT]
    kinds = [a["args"]["kind"] for a in anchors]
    assert kinds[0] == "placement" and kinds.count("placement") == 1
    assert kinds.count("flush_boundary") >= len(boundaries)
    assert [a["args"]["anchor"] for a in anchors] == list(
        range(1, len(anchors) + 1)
    )


def test_sidecar_exposes_recorder_dropped_records(tmp_path):
    """Satellite: FlightRecorder.dropped (ring evictions — truncated
    trace.json/watchdog snapshots) must be an operator-visible gauge on
    the /metrics sidecar, wired by RunObservability."""
    import types
    import urllib.request as _url

    from simclr_pytorch_distributed_tpu.utils.obs import RunObservability

    cfg = types.SimpleNamespace(
        save_folder=str(tmp_path), flight_recorder="on", watchdog_secs=0,
        metrics_port=0, metrics_host="127.0.0.1",
    )
    # port 0 means "no sidecar" to the config surface; give a real
    # ephemeral-port server by patching after construction is overkill —
    # bind one directly through the same wiring with a truthy port
    server = None
    try:
        cfg.metrics_port = _free_port()
        obs = RunObservability(cfg, name="test")
        server = obs.sidecar
        assert obs.recorder is not None and obs.gauges is not None
        # the gauge is lazy (scrape-time read of recorder.dropped), so a
        # simulated saturation is visible without filling the real ring
        obs.recorder.dropped = 5
        host, port = server.server_address[:2]
        with _url.urlopen(f"http://{host}:{port}/metrics", timeout=10) as r:
            body = r.read().decode()
        assert "train_recorder_dropped_records 5" in body
    finally:
        if server is not None:
            obs.close()


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_obs_closes_on_placement_rejection(tmp_path):
    """Review fix: the obs stack now builds BEFORE make_store, so the
    placement rejection (a designed startup raise) must still close it —
    recorder exported, terminal run_exit stamped — on exactly the
    startup-failure run whose post-mortem the stack exists to capture."""
    from simclr_pytorch_distributed_tpu import config as config_lib
    from simclr_pytorch_distributed_tpu.train import supcon as supcon_driver

    cfg = config_lib.SupConConfig(
        model="resnet10", dataset="synthetic", batch_size=32, epochs=1,
        learning_rate=0.05, workdir=str(tmp_path), seed=0, method="SimCLR",
        data_placement="device", device_budget_mb=1,  # 6.3MB set: rejected
        flight_recorder="on",
    )
    cfg = config_lib.finalize_supcon(cfg)
    with pytest.raises(ValueError, match="device"):
        supcon_driver.run(cfg)
    events_path = os.path.join(cfg.save_folder, "events.jsonl")
    events = [json.loads(x) for x in open(events_path).read().splitlines()]
    (exit_ev,) = [e for e in events if e["name"] == "run_exit"]
    assert exit_ev["args"]["code"] == 1  # plain-crash code for ValueError
    assert os.path.exists(os.path.join(cfg.save_folder, "trace.json"))
    # the stack is closed: the module-level recorder is uninstalled
    assert tracing.current() is None


def test_obs_staged_resets_watchdog_deadline(tmp_path):
    """Review fix: the obs stack now builds BEFORE make_store (so the
    placement collective runs under the armed watchdog), which put the
    store's one-time dataset upload inside the first watchdog window —
    staged() beats after staging so that time no longer counts against
    --watchdog_secs (a spurious staging dump reads as a stall to the
    supervisor)."""
    import types

    from simclr_pytorch_distributed_tpu.utils.obs import RunObservability

    cfg = types.SimpleNamespace(
        save_folder=str(tmp_path), flight_recorder="off", watchdog_secs=30,
        metrics_port=0, metrics_host="127.0.0.1",
    )
    obs = RunObservability(cfg, name="t")
    try:
        wd = obs.watchdog
        wd.close()  # drive check() on a fake clock, not the poll thread
        clk = FakeClock()
        wd._clock = clk
        wd._last = clk()
        clk.advance(wd.deadline_s + 1)  # "staging took longer than the deadline"
        obs.staged()
        assert not wd.check()  # staging time no longer counts
        clk.advance(wd.deadline_s + 1)
        assert wd.check()  # a real post-staging stall still fires
    finally:
        obs.close()


def test_run_paths_rotate_per_session(tmp_path):
    """A resumed run (exit-75 relaunch into the SAME save_folder) must not
    append a second ts~0 timeline into the first session's events.jsonl —
    each session gets a fresh _rK file, one self-consistent timeline per
    file (trace_report consumes them independently)."""
    e1, t1 = tracing.run_paths(str(tmp_path))
    assert os.path.basename(e1) == "events.jsonl"
    open(e1, "w").write("{}\n")
    e2, t2 = tracing.run_paths(str(tmp_path))
    assert os.path.basename(e2) == "events_r2.jsonl"
    assert os.path.basename(t2) == "trace_r2.json"
    open(e2, "w").write("{}\n")
    e3, _ = tracing.run_paths(str(tmp_path))
    assert os.path.basename(e3) == "events_r3.jsonl"
    # pod processes rotate independently under their own _pN prefix
    ep, tp = tracing.run_paths(str(tmp_path), process_index=1)
    assert os.path.basename(ep) == "events_p1.jsonl"
    assert os.path.basename(tp) == "trace_p1.json"


# ------------------------------------------------ set-up on the record


@pytest.fixture
def before_any_install(monkeypatch):
    """The module as a fresh process has it: no recorder, the buffer open,
    imports not yet recorded; the set-up clock and the process's start are
    the test's to set."""
    monkeypatch.setattr(tracing, "_current", None)
    monkeypatch.setattr(tracing, "_early", [])
    monkeypatch.setattr(tracing, "_imports_recorded", False)
    monkeypatch.setattr(tracing, "_started_after_boot", lambda: None)
    clk = FakeClock(50.0)
    monkeypatch.setattr(tracing, "_setup_clock", clk)
    return clk


def test_records_before_the_first_install_wait_and_are_rebased(before_any_install):
    clk = before_any_install
    with tracing.span("store", track=tracing.SETUP_TRACK):
        clk.advance(2.0)
    tracing.record_span("trace", tracing.COMPILE_TRACK, 52.5, 53.0, fun_name="f")
    tracing.record_event("mark", tracing.SETUP_TRACK, 51.0, at="an attribute")
    tracing.event("package_import", track=tracing.SETUP_TRACK)
    tracing.event("y", track="main:flush")  # not a set-up track: dropped
    with tracing.span("x", track="main:compile"):
        clk.advance(1.0)
    for _ in range(tracing.EARLY_RECORDS_MAX + 40):  # the bound holds
        tracing.event("filler", track=tracing.COMPILE_TRACK)
    assert len(tracing._early) == tracing.EARLY_RECORDS_MAX
    clk.advance(4.0)  # the recorder starts at 57.0
    rec = tracing.FlightRecorder(clock=clk)
    tracing.install(rec)
    try:
        snap = rec.snapshot()
        assert [e["name"] for e in snap[:4]] == ["store", "trace", "mark", "package_import"]
        assert len(snap) == tracing.EARLY_RECORDS_MAX
        store, trace, named, mark = snap[:4]
        assert named["ts"] == pytest.approx(-6.0) and named["args"] == {"at": "an attribute"}
        assert store["ts"] == pytest.approx(-7.0) and store["dur"] == pytest.approx(2.0)
        assert trace["ts"] == pytest.approx(-4.5) and trace["args"] == {"fun_name": "f"}
        assert mark["ph"] == "i" and mark["ts"] == pytest.approx(-5.0)
    finally:
        tracing.uninstall()
    # after the first install nothing waits any more: no recorder, no work
    tracing.event("late", track=tracing.SETUP_TRACK)
    with tracing.span("late_span", track=tracing.SETUP_TRACK):
        pass
    assert tracing._early is None
    rec2 = tracing.FlightRecorder(clock=clk)
    tracing.install(rec2)
    tracing.uninstall()
    assert rec2.snapshot() == []


def test_the_first_install_of_none_or_another_clock_drops_the_buffer(before_any_install):
    tracing.event("package_import", track=tracing.SETUP_TRACK)
    other = tracing.FlightRecorder(clock=FakeClock(3.0))  # not the set-up clock
    tracing.install(other)
    tracing.uninstall()
    assert other.snapshot() == [] and tracing._early is None
    tracing.install(None)  # a second first install finds nothing
    assert tracing._early is None


def test_imports_done_records_the_import_span_once(before_any_install, monkeypatch):
    import simclr_pytorch_distributed_tpu as package

    clk = before_any_install
    monkeypatch.setattr(package, "IMPORT_STARTED", 51.0)
    clk.advance(3.0)
    tracing.imports_done()
    clk.advance(1.0)
    tracing.imports_done()  # once a process
    rec = tracing.FlightRecorder(clock=clk)
    tracing.install(rec)
    tracing.uninstall()
    mark, span = rec.snapshot()
    assert (mark["name"], mark["ts"]) == (tracing.PACKAGE_IMPORT, pytest.approx(-3.0))
    assert span["name"] == "import" and span["track"] == tracing.SETUP_TRACK
    assert span["ts"] == pytest.approx(-3.0) and span["dur"] == pytest.approx(2.0)


def test_process_start_is_negative_and_no_older_than_the_interpreter(tmp_path):
    """A fresh interpreter's recorder: its ``process_start`` lies before the
    interpreter's first line and after the moment it was launched, to the
    kernel's tick. ``tracing`` is loaded from its file: stdlib only."""
    import subprocess
    import sys

    if tracing.process_start() is None:
        pytest.skip("no /proc/self/stat here")
    tick = 1.0 / os.sysconf("SC_CLK_TCK")
    script = tmp_path / "child.py"
    script.write_text(
        "import time\n"
        "first = time.monotonic()\n"
        "import importlib.util, json\n"
        f"spec = importlib.util.spec_from_file_location('t', {tracing.__file__!r})\n"
        "t = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(t)\n"
        "rec = t.FlightRecorder()\n"
        "(start,) = [e for e in rec.snapshot() if e['name'] == 'process_start']\n"
        "print(json.dumps({'first': first, 'ts': start['ts'], 't0': rec._t0,\n"
        "                  'track': start['track']}))\n"
    )
    launched = time.monotonic()
    out = subprocess.run([sys.executable, str(script)], capture_output=True, text=True,
                         timeout=120, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    started = got["t0"] + got["ts"]
    assert got["track"] == tracing.SETUP_TRACK and got["ts"] < 0
    assert launched - tick <= started <= got["first"] + tick
    # and this process's recorders say the same of this process
    rec = tracing.FlightRecorder()
    (start,) = [e for e in rec.snapshot() if e["name"] == tracing.PROCESS_START]
    assert start["ts"] < 0 and rec._t0 + start["ts"] < launched


def test_a_small_jit_records_trace_lower_and_compile_spans():
    """One program: its trace, lowering and backend compile, spans on track
    ``compile`` with ``fun_name``; the compile says whether the persistent
    cache answered (``cache_hit``), and no separate hit event is left."""
    import jax
    import jax.numpy as jnp

    tracing.forward_compile_events()
    rec = tracing.FlightRecorder(clock=FakeClock())  # the spans carry JAX's times
    tracing.install(rec)
    try:
        def spanned_small(v):
            return jnp.sin(v) * 2.0

        jax.jit(spanned_small)(np.ones(3, np.float32))
        # the hit flag: set by the cache's event inside a compile, read by
        # that compile's span, and not carried to the next
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", 10.0, 10.5, fun_name="jit(spanned_hit)")
        jax.monitoring.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", 11.0, 11.25, fun_name="jit(spanned_miss)")
    finally:
        tracing.uninstall()
    events = rec.snapshot()
    mine = {e["name"]: e for e in events if "spanned_small" in e.get("args", {}).get("fun_name", "")}
    assert set(mine) == {"trace", "lower", "backend_compile"}
    assert all(e["ph"] == "X" and e["track"] == tracing.COMPILE_TRACK for e in mine.values())
    assert mine["trace"]["ts"] <= mine["lower"]["ts"] <= mine["backend_compile"]["ts"]
    args = mine["backend_compile"]["args"]
    assert args["cache_hit"] is False and args["duration_s"] == mine["backend_compile"]["dur"]
    hit, miss = (next(e["args"] for e in events if e.get("args", {}).get("fun_name") == name)
                 for name in ("jit(spanned_hit)", "jit(spanned_miss)"))
    assert hit["cache_hit"] is True and hit["duration_s"] == 0.5
    assert miss["cache_hit"] is False
    assert not [e for e in events if e["name"] == "cache_hit"]


def test_bare_package_import_imports_neither_jax_nor_utils():
    import subprocess
    import sys

    code = ("import sys; before = set(sys.modules); import simclr_pytorch_distributed_tpu; "
            "print(sorted(set(sys.modules) - before))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         timeout=120, check=True, cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.stdout.strip() == "['simclr_pytorch_distributed_tpu']"


def _trace_report():
    import importlib
    import sys

    scripts = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts")
    sys.path.insert(0, scripts)
    try:
        return importlib.import_module("trace_report")
    finally:
        sys.path.remove(scripts)


def test_setup_table_sums_to_its_wall():
    """Process start at -10 to the first flush boundary at 6: nested traces
    count once, a compile inside ``store`` counts as compile, the first
    step's trace and compile as theirs and its rest as ``first_step``."""
    tr = _trace_report()

    def x(name, track, ts, dur, **args):
        return {"name": name, "track": track, "ph": "X", "ts": ts, "dur": dur, "args": args}

    events = [
        {"name": "process_start", "track": "setup", "ph": "i", "ts": -10.0},
        {"name": "package_import", "track": "setup", "ph": "i", "ts": -9.0},
        x("import", "setup", -9.0, 5.0),
        x("backend_start", "setup", -3.5, 1.5),
        x("store", "setup", -1.0, 2.0),
        x("backend_compile", "compile", 0.0, 0.5, fun_name="jit(gather)", cache_hit=True),
        x("trace", "compile", 2.0, 1.0, fun_name="f"),
        x("trace", "compile", 2.2, 0.3, fun_name="sin"),  # nested: counted once
        x("lower", "compile", 3.0, 0.5, fun_name="jit(f)"),
        x("backend_compile", "compile", 3.5, 1.0, fun_name="jit(f)", cache_hit=False),
        x("first_step", "main:compile", 1.5, 3.5, step=0),
        x("tb_writer", "setup", 1.0, 0.25),
        x("flush_boundary", "main:flush", 6.0, 0.1, steps=2),
        x("flush_boundary", "main:flush", 7.0, 0.1, steps=2),
    ]
    setup = tr.build_setup(events)
    rows = setup["rows"]
    assert setup["wall_s"] == 16.0 and sum(rows.values()) == pytest.approx(16.0)
    assert rows["compile"] == pytest.approx(1.5) and rows["trace_lower"] == pytest.approx(1.5)
    assert rows["boot"] == pytest.approx(1.0) and rows["import"] == pytest.approx(5.0)
    assert rows["backend_start"] == pytest.approx(1.5) and rows["tb_writer"] == pytest.approx(0.25)
    assert rows["store"] == pytest.approx(1.5)  # its 0.5 of compile is compile's
    # the first step less its trace, lowering and compile: [1.5, 2] and [4.5, 5]
    assert rows["first_step"] == pytest.approx(1.0) and rows["first_window"] == pytest.approx(1.0)
    assert rows["other"] == pytest.approx(16.0 - 14.25)
    assert [p["fun_name"] for p in setup["programs"]] == ["jit(f)", "jit(gather)"]
    assert setup["programs"][1]["cache_hits"] == 1
    text = tr.render_setup(setup)
    assert "backend_start" in text and "tb_writer" in text and "1 hit, 0 miss" in text
    # the attribution table still starts at the recorder's own start
    report = tr.build_report(events)
    assert report["consistency"]["wall_s"] == pytest.approx(7.1) and report["consistency"]["ok"]


def test_main_supcon_run_puts_its_setup_on_the_record(tmp_path):
    """An operator's run of ``main_supcon.py``, in a process of its own:
    ``events.jsonl`` opens at the process's start, holds the set-up spans
    (``import``, ``backend_start``, ``store``, ``tb_writer``), none of which
    overlaps another, and every compile as spans with ``cache_hit``; and
    ``trace_report.py`` prints the set-up table beside an attribution that
    still holds."""
    import glob
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, XLA_FLAGS="", JAX_PLATFORMS="cpu")
    subprocess.run(
        [sys.executable, "main_supcon.py", "--dataset", "synthetic", "--epochs", "1",
         "--batch_size", "256", "--size", "8", "--model", "resnet10", "--learning_rate",
         "0.05", "--temp", "0.5", "--method", "SimCLR", "--print_freq", "3",
         "--save_freq", "5", "--workdir", str(tmp_path)],
        cwd=root, env=env, check=True, timeout=600, capture_output=True)
    (path,) = glob.glob(str(tmp_path / "**" / "events.jsonl"), recursive=True)
    events = tracing.load_events_jsonl(path)
    assert events[0]["name"] == tracing.PROCESS_START and events[0]["ts"] < 0
    spans = sorted((e for e in events if e["track"] == tracing.SETUP_TRACK and e["ph"] == "X"),
                   key=lambda e: e["ts"])
    assert {"import", "backend_start", "store", "tb_writer"} <= {e["name"] for e in spans}
    for a, b in zip(spans, spans[1:]):
        assert b["ts"] >= a["ts"] + a["dur"] - 1e-6, (a, b)
    compiles = [e for e in events if e["name"] == "backend_compile"]
    assert compiles and all(e["ph"] == "X" and "fun_name" in e["args"]
                            and isinstance(e["args"]["cache_hit"], bool) for e in compiles)
    assert not [e for e in events if e["name"] == "cache_hit"]
    out = subprocess.run([sys.executable, "scripts/trace_report.py", "--events", path],
                         cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr  # consistency.ok
    assert "set-up, process start to the first flush boundary" in out.stdout
    for row in ("boot", "import", "backend_start", "store", "tb_writer", "compile"):
        assert f"\n  {row} " in out.stdout, out.stdout
