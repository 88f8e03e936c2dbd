"""The fleet supervisor (simclr_pytorch_distributed_tpu/supervise/).

Three layers, matching the package split:

- the PURE decision policy, enumerated exhaustively (exit-code table,
  precedence, backoff growth/cap, budget, resize upgrade) — no processes;
- the signal collectors: Prometheus text parsing (round-tripped through
  utils/prom.render_prometheus — parser and renderer must agree), the
  incremental run-dir watcher, resume-dir resolution, the topology env
  rewrite;
- the LOOP against scripted children (the test_launchers stub pattern,
  python edition): exit-code sequences drive real Popen children, and the
  supervisor's decisions + events.jsonl records are asserted end to end.

The real-driver scenarios (SIGKILL / stall / collapse / resize against the
actual pretrain loop) live in tests/test_fault_injection.py and
scripts/supervisor_matrix.py; the committed evidence artifact their matrix
produced is gate-checked here through ratchet's pure
``supervisor_gate_record``.
"""

import json
import os
import sys
import threading

import pytest

from simclr_pytorch_distributed_tpu.supervise import launch, observe, policy
from simclr_pytorch_distributed_tpu.supervise.supervisor import (
    SuperviseConfig,
    Supervisor,
)
from simclr_pytorch_distributed_tpu.utils import prom, tracing

pytestmark = pytest.mark.supervisor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ policy


def obs(rc, **kw):
    return policy.ExitObservation(returncode=rc, **kw)


def test_policy_exit_code_table():
    """The full classification table, one fresh policy per row."""
    rows = {
        0: policy.DONE,
        75: policy.RESTART,
        3: policy.GIVE_UP,             # health abort: never retried
        1: policy.BACKOFF_RESTART,     # NaN / unhandled crash
        2: policy.BACKOFF_RESTART,     # flush (I/O) failure
        -9: policy.BACKOFF_RESTART,    # SIGKILL
        -15: policy.BACKOFF_RESTART,   # SIGTERM death
        7: policy.BACKOFF_RESTART,     # unclassified nonzero
    }
    for rc, action in rows.items():
        p = policy.DecisionPolicy(max_restarts=3)
        assert p.decide(obs(rc)).action == action, rc


def test_policy_health_abort_outranks_budget_and_never_retries():
    """Exit 3 gives up even with a full budget left (collapse lives in the
    weights — the RESILIENCE.md precedence note), and also after restarts."""
    p = policy.DecisionPolicy(max_restarts=100)
    d = p.decide(obs(3))
    assert d.action == policy.GIVE_UP and "collapse" in d.reason


def test_policy_restart_budget_bounds_every_failure_class():
    p = policy.DecisionPolicy(max_restarts=2, backoff_base_s=0.1)
    assert p.decide(obs(75)).action == policy.RESTART
    assert p.decide(obs(-9)).action == policy.BACKOFF_RESTART
    d = p.decide(obs(1))
    assert d.action == policy.GIVE_UP and "budget" in d.reason
    # ...and 0 still reports done after exhaustion
    assert p.decide(obs(0)).action == policy.DONE


def test_policy_backoff_grows_exponentially_and_caps():
    p = policy.DecisionPolicy(
        max_restarts=100, backoff_base_s=1.0, backoff_max_s=5.0
    )
    delays = [p.decide(obs(-9)).delay_s for _ in range(5)]
    assert delays == [1.0, 2.0, 4.0, 5.0, 5.0]  # 2^k then the cap


def test_policy_clean_preemption_resets_failure_streak():
    p = policy.DecisionPolicy(max_restarts=100, backoff_base_s=1.0)
    p.decide(obs(-9))
    p.decide(obs(-9))
    assert p.decide(obs(-9)).delay_s == 4.0
    assert p.decide(obs(75)).delay_s == 0.0   # no backoff on preemption
    # streak reset: the next failure waits the base again
    assert p.decide(obs(-9)).delay_s == 1.0


def test_policy_pending_resize_upgrades_any_restartable_exit():
    """The resize request is the OPERATOR'S — it must survive whichever
    exit happens to land first (clean preempt or a crash), and it is
    consumed exactly once."""
    p = policy.DecisionPolicy(max_restarts=10)
    p.request_resize(4)
    d = p.decide(obs(75))
    assert d.action == policy.RESTART_RESIZED and d.devices == 4
    assert p.decide(obs(75)).action == policy.RESTART  # consumed

    p2 = policy.DecisionPolicy(max_restarts=10)
    p2.request_resize(2)
    d2 = p2.decide(obs(-9))
    assert d2.action == policy.RESTART_RESIZED and d2.devices == 2
    assert d2.delay_s > 0  # the crash's backoff still applies


def test_policy_stalled_observation_reason_names_the_kill():
    p = policy.DecisionPolicy(max_restarts=3)
    d = p.decide(obs(-9, stalled=True, stall_dumps=2))
    assert d.action == policy.BACKOFF_RESTART and "stalled" in d.reason


def test_policy_rejects_bad_config():
    with pytest.raises(ValueError):
        policy.DecisionPolicy(max_restarts=-1)
    with pytest.raises(ValueError):
        policy.DecisionPolicy(backoff_base_s=0.0)
    with pytest.raises(ValueError):
        policy.DecisionPolicy(backoff_base_s=2.0, backoff_max_s=1.0)
    with pytest.raises(ValueError):
        policy.DecisionPolicy().request_resize(0)


# ------------------------------------------------- the straggler ladder rows


def sobs(rc=75, **kw):
    """A mitigation-preempt exit: the supervisor gracefully preempted the
    child after a persistence verdict naming host 1 of 2 at 150 ms skew."""
    kw.setdefault("straggler_persistent", True)
    kw.setdefault("straggler_host", 1)
    kw.setdefault("straggler_skew_s", 0.15)
    kw.setdefault("processes", 2)
    return obs(rc, **kw)


@pytest.mark.chaos
def test_policy_extended_table_with_straggler_verdict():
    """The FULL extended classification table: every exit-code row crossed
    with a pending persistence verdict. Only the clean mitigation preempt
    (75, not stalled) takes the ladder; every other row keeps its
    pre-ladder decision — the verdict rides along as context, never as an
    override."""
    rows = {
        0: policy.DONE,                    # completed: no mitigation needed
        75: policy.RESTART_REBALANCED,     # the ladder's first rung
        3: policy.GIVE_UP,                 # health abort still outranks all
        1: policy.BACKOFF_RESTART,         # crash before the preempt landed
        2: policy.BACKOFF_RESTART,
        -9: policy.BACKOFF_RESTART,        # mitigation SIGTERM lapsed to KILL
        -15: policy.BACKOFF_RESTART,
        7: policy.BACKOFF_RESTART,
    }
    for rc, action in rows.items():
        p = policy.DecisionPolicy(max_restarts=10)
        assert p.decide(sobs(rc)).action == action, rc
        # the ladder only advanced on the one row that took it
        assert p.straggler_level == (1 if action == policy.RESTART_REBALANCED
                                     else 0), rc


@pytest.mark.chaos
def test_policy_straggler_ladder_escalates_then_gives_up():
    """Rung by rung: rebalance (share hint) -> exclude (topology minus the
    slow host) -> give_up, with the budget charged per rung."""
    p = policy.DecisionPolicy(max_restarts=10)
    d1 = p.decide(sobs())
    assert d1.action == policy.RESTART_REBALANCED
    assert d1.share == "1:0.5" and d1.devices is None
    assert "rebalancing" in d1.reason and d1.delay_s == 0.0
    d2 = p.decide(sobs())
    assert d2.action == policy.RESTART_RESIZED
    assert d2.devices == 1 and d2.share is None   # 2 processes minus host 1
    assert "excluding" in d2.reason
    d3 = p.decide(sobs())
    assert d3.action == policy.GIVE_UP and "ladder exhausted" in d3.reason
    assert p.restarts == 2  # give_up never burned budget


@pytest.mark.chaos
def test_policy_straggler_unknown_fleet_size_excludes_without_topology():
    """A verdict without a process count (older sidecar) still escalates,
    but the exclusion rung cannot compute a topology — devices stays None
    (inherit), the scheduler-level realization."""
    p = policy.DecisionPolicy(max_restarts=10)
    p.decide(sobs())
    d = p.decide(sobs(processes=0))
    assert d.action == policy.RESTART_RESIZED and d.devices is None


@pytest.mark.chaos
def test_policy_clean_preempt_without_verdict_resets_the_ladder():
    """Recovery: a later clean preemption with NO verdict in force means
    the rebalance worked — a straggler relapse starts the ladder at
    rebalance again instead of escalating straight to exclusion."""
    p = policy.DecisionPolicy(max_restarts=10)
    assert p.decide(sobs()).action == policy.RESTART_REBALANCED
    assert p.decide(obs(75)).action == policy.RESTART  # healthy preempt
    assert p.straggler_level == 0
    assert p.decide(sobs()).action == policy.RESTART_REBALANCED  # rung 1 again


@pytest.mark.chaos
def test_policy_pending_operator_resize_outranks_mitigation():
    """Both landing on the same exit: the operator's explicit resize wins,
    consumes the pending target, and the ladder does NOT advance — the
    next verdict still starts at rebalance."""
    p = policy.DecisionPolicy(max_restarts=10)
    p.request_resize(4)
    d = p.decide(sobs())
    assert d.action == policy.RESTART_RESIZED and d.devices == 4
    assert "explicit request wins" in d.reason
    assert p.pending_resize is None and p.straggler_level == 0
    assert p.decide(sobs()).action == policy.RESTART_REBALANCED


@pytest.mark.chaos
def test_policy_budget_caps_the_straggler_ladder():
    """Mitigation restarts draw from the SAME budget as every other class
    (the PREEMPT_RETRIES contract): an exhausted budget turns a verdict
    into give_up before the ladder is consulted."""
    p = policy.DecisionPolicy(max_restarts=1, backoff_base_s=0.1)
    assert p.decide(obs(-9)).action == policy.BACKOFF_RESTART
    d = p.decide(sobs())
    assert d.action == policy.GIVE_UP and "budget" in d.reason
    p0 = policy.DecisionPolicy(max_restarts=0)
    assert p0.decide(sobs()).action == policy.GIVE_UP


@pytest.mark.chaos
def test_policy_stall_kill_outranks_straggler_verdict():
    """A 75 forced by the supervisor's own STALL kill is a failure even
    with a verdict pending: the stall row wins (backoff, no ladder) — a
    wedged child must not be rewarded with a rebalance."""
    p = policy.DecisionPolicy(max_restarts=10, backoff_base_s=1.0)
    d = p.decide(sobs(stalled=True, stall_dumps=1))
    assert d.action == policy.BACKOFF_RESTART and "stalled" in d.reason
    assert p.straggler_level == 0


# ------------------------------------------------------- the straggler tracker


def skew_gauges(step, skew=0.2, straggler=1, processes=2):
    g = {
        "train_step": float(step),
        observe.SKEW_GAUGE: float(skew),
        observe.PROC_COUNT_GAUGE: float(processes),
    }
    if straggler is not None:
        g[observe.STRAGGLER_GAUGE] = float(straggler)
    return g


@pytest.mark.chaos
def test_tracker_k_of_n_verdict_and_consume():
    t = observe.StragglerTracker(0.1, persist_k=3, window_n=5,
                                 clock=lambda: 42.0)
    for step in (1, 2):
        f = t.observe(skew_gauges(step))
        assert f is not None and f["straggler"] == 1
        assert t.take_persistent() is None  # hysteresis: K not reached
    t.observe(skew_gauges(3))
    v = t.take_persistent()
    assert v is not None
    assert v["straggler"] == 1 and v["votes"] == 3 and v["window"] == 3
    assert v["at"] == 42.0 and v["processes"] == 2 and v["share"] == 0.5
    # consuming resets: detection starts fresh
    assert t.take_persistent() is None
    t.observe(skew_gauges(4))
    assert t.take_persistent() is None


@pytest.mark.chaos
def test_tracker_scrapes_dedup_on_the_step_gauge():
    """The skew gauge holds its value between flush boundaries, so many
    scrapes of one boundary must count ONCE — per-poll counting would
    convert one skewed boundary into an instant verdict."""
    t = observe.StragglerTracker(0.1, persist_k=3, window_n=5)
    assert t.observe(skew_gauges(7)) is not None
    for _ in range(10):
        assert t.observe(skew_gauges(7)) is None  # same boundary
    assert t.take_persistent() is None
    # a scrape with NO step gauge still dedups (None == None), not crash
    g = skew_gauges(0)
    del g["train_step"]
    assert t.observe(dict(g)) is not None
    assert t.observe(dict(g)) is None


@pytest.mark.chaos
def test_tracker_below_bar_boundaries_dilute_the_vote():
    """Recovery hysteresis: below-bar boundaries enter the window as
    non-votes, so a host that recovered walks itself back out instead of
    being convicted on stale evidence."""
    t = observe.StragglerTracker(0.1, persist_k=3, window_n=3)
    t.observe(skew_gauges(1))
    t.observe(skew_gauges(2))
    # recovered: two clean boundaries push the spikes out of the window
    t.observe(skew_gauges(3, skew=0.0))
    t.observe(skew_gauges(4, skew=0.0))
    t.observe(skew_gauges(5))
    assert t.take_persistent() is None  # only 1 vote in the last 3


@pytest.mark.chaos
def test_tracker_identity_hop_never_convicts_anyone():
    """Skew whose straggler identity hops between hosts is load imbalance,
    not a sick host: no single host accumulates K votes (a 3-host
    rotation caps any one host at 2 votes in a 5-boundary window)."""
    t = observe.StragglerTracker(0.1, persist_k=3, window_n=5)
    for step in range(1, 13):
        t.observe(skew_gauges(step, straggler=step % 3, processes=3))
        assert t.take_persistent() is None


@pytest.mark.chaos
def test_tracker_single_process_and_missing_identity_are_benign():
    """No identity gauges (older sidecar) or a single-process fleet: the
    finding may still fire (warn), but no vote is ever cast — there is no
    host to rebalance away from."""
    t = observe.StragglerTracker(0.1, persist_k=1, window_n=1)
    assert t.observe(None) is None
    assert t.observe({}) is None
    # single process: identity -1, count 1 (what telemetry publishes)
    f = t.observe(skew_gauges(1, straggler=-1, processes=1))
    assert f is not None and "straggler" not in f
    assert t.take_persistent() is None
    # multi-process but the identity gauge is absent entirely
    f2 = t.observe(skew_gauges(2, straggler=None))
    assert f2 is not None and "straggler" not in f2
    assert t.take_persistent() is None
    # identity present but the fleet-size gauge says single: still benign
    g = skew_gauges(3, straggler=0, processes=1)
    assert t.observe(g) is not None
    assert t.take_persistent() is None
    # the disabled tracker (bar 0) observes nothing at all
    t0 = observe.StragglerTracker(0.0, persist_k=1, window_n=1)
    assert t0.observe(skew_gauges(1)) is None
    assert t0.take_persistent() is None


@pytest.mark.chaos
def test_tracker_reset_clears_stale_votes():
    """A new child attempt restarts its gauge stream: reset() must drop
    accumulated votes AND the step dedup, or attempt 1's skew would
    convict attempt 2 on its first boundary."""
    t = observe.StragglerTracker(0.1, persist_k=3, window_n=5)
    t.observe(skew_gauges(1))
    t.observe(skew_gauges(2))
    t.reset()
    assert t.observe(skew_gauges(2)) is not None  # same step: dedup cleared
    t.observe(skew_gauges(3))
    assert t.take_persistent() is None  # old votes gone: only 2 of 3


@pytest.mark.chaos
def test_tracker_rejects_bad_config():
    with pytest.raises(ValueError):
        observe.StragglerTracker(1.0, persist_k=0)
    with pytest.raises(ValueError):
        observe.StragglerTracker(1.0, persist_k=3, window_n=2)


# ----------------------------------------------------------------- observe


def test_parse_prometheus_roundtrips_render():
    """The parser must invert utils/prom's renderer for the unlabeled gauge
    lines the trainer sidecar emits (labeled histogram series are skipped,
    not misparsed)."""
    text = prom.render_prometheus([
        ("train_step", None, 120),
        ("train_last_boundary_age_seconds", None, 3.25),
        ("train_exit_code", None, 75),
        ("lat_bucket", {"bucket": "b8", "le": "5"}, 3),  # labeled: skipped
    ])
    parsed = observe.parse_prometheus_text(text + "# HELP noise\nbad line x\n")
    assert parsed == {
        "train_step": 120.0,
        "train_last_boundary_age_seconds": 3.25,
        "train_exit_code": 75.0,
    }


def test_scraper_scrapes_a_real_trainer_sidecar():
    """End-to-end against the REAL sidecar server: the supervisor-facing
    gauges (start_time_seconds at construction, exit_code terminal stamp)
    come back through HTTP exactly as TrainerGauges rendered them."""
    g = prom.TrainerGauges(wall_clock=lambda: 1234.5)
    g.beat(7)
    g.set_exit_code(75)
    server = prom.start_metrics_server(0, g.prometheus_text, host="127.0.0.1")
    try:
        port = server.server_address[1]
        scraped = observe.MetricsScraper(port).scrape()
        assert scraped["train_step"] == 7.0
        assert scraped["train_start_time_seconds"] == 1234.5
        assert scraped["train_exit_code"] == 75.0
        assert scraped["train_last_boundary_age_seconds"] >= 0.0
    finally:
        server.shutdown()


def test_scraper_dead_sidecar_returns_none():
    import socket

    with socket.socket() as s:  # grab then release a port: nothing listens
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    assert observe.MetricsScraper(port, timeout_s=0.2).scrape() is None


def test_run_dir_watcher_is_incremental(tmp_path):
    run_dir = tmp_path / "run"
    w = observe.RunDirWatcher(str(run_dir))
    assert w.poll() == ([], [], [])  # dir not there yet: not an error

    run_dir.mkdir()
    (run_dir / "stall_dump_1.txt").write_text("stacks")
    events = run_dir / "events.jsonl"
    events.write_text(
        json.dumps({"name": "health_alarm", "args": {"step": 5}}) + "\n"
        + json.dumps({"name": "flush_boundary"}) + "\n"  # not watched
    )
    (run_dir / "ckpt_epoch_1").mkdir()
    (run_dir / "ckpt_epoch_1" / "meta.json").write_text("{}")

    dumps, evs, ckpts = w.poll()
    assert [os.path.basename(d) for d in dumps] == ["stall_dump_1.txt"]
    assert [e["name"] for e in evs] == ["health_alarm"]
    assert ckpts == ["ckpt_epoch_1"]
    assert w.poll() == ([], [], [])  # nothing new -> nothing reported

    # appends surface; a torn (unterminated) last line is deferred, not lost
    with open(events, "a") as f:
        f.write(json.dumps({"name": "preempt_exit"}) + "\n")
        f.write('{"name": "nan_rollb')  # mid-write race
    _, evs, _ = w.poll()
    assert [e["name"] for e in evs] == ["preempt_exit"]
    with open(events, "a") as f:
        f.write('ack"}\n')
    _, evs, _ = w.poll()
    assert [e["name"] for e in evs] == ["nan_rollback"]

    # resumed sessions open rotated files (events_r2.jsonl): watched too
    (run_dir / "events_r2.jsonl").write_text(
        json.dumps({"name": "stall_detected"}) + "\n"
    )
    _, evs, _ = w.poll()
    assert [(e["name"], e["_file"]) for e in evs] == [
        ("stall_detected", "events_r2.jsonl")
    ]


# ------------------------------------------------------------------ launch


def test_find_resume_dir_newest_excluding_probe_and_ce(tmp_path):
    ws = tmp_path / "ws"
    assert launch.find_resume_dir(str(ws)) is None  # no workdir yet
    old = ws / "cifar10_models" / "cifar10_0101_0000_run"
    new = ws / "synthetic_models" / "synthetic_0102_0000_run"
    for d in (old, new):
        d.mkdir(parents=True)
    os.utime(old, (1000, 1000))
    far = 4102444800
    # probe/CE decoys newer than everything must not win (the launcher's
    # awk-filter contract, now in tested python)
    for decoy in ("classifier_0103_0000_x", "ce_0103_0000_y"):
        d = ws / "cifar10_models" / decoy
        d.mkdir()
        os.utime(d, (far, far))
    assert launch.find_resume_dir(str(ws)) == str(new)


def test_topology_env_rewrites_xla_flags_idempotently():
    base = {"XLA_FLAGS": "--foo=1 --xla_force_host_platform_device_count=8",
            "OTHER": "x"}
    env = launch.topology_env(4, base)
    assert env["XLA_FLAGS"] == "--foo=1 --xla_force_host_platform_device_count=4"
    assert env["OTHER"] == "x"
    # append when absent; None leaves the env alone
    env2 = launch.topology_env(2, {"XLA_FLAGS": "--foo=1"})
    assert env2["XLA_FLAGS"] == "--foo=1 --xla_force_host_platform_device_count=2"
    env3 = launch.topology_env(2, {})
    assert env3["XLA_FLAGS"] == "--xla_force_host_platform_device_count=2"
    assert "XLA_FLAGS" not in launch.topology_env(None, {"A": "b"})


def test_build_command_appends_resume_last_wins():
    cmd = launch.build_command(
        ["python", "main_supcon.py", "--resume", "stale"], "/fresh"
    )
    assert cmd.index("stale") < cmd.index("/fresh")  # argparse last-wins
    assert launch.build_command(["x"], None) == ["x"]


@pytest.mark.chaos
def test_share_env_sets_and_clears_the_rebalance_hint():
    base = {launch.FLEET_SHARE_ENV: "0:0.25", "OTHER": "x"}
    env = launch.share_env("1:0.5", base)
    assert env[launch.FLEET_SHARE_ENV] == "1:0.5" and env["OTHER"] == "x"
    # None REMOVES a stale hint (post-exclusion/resize shares are uniform
    # again) rather than inheriting it
    assert launch.FLEET_SHARE_ENV not in launch.share_env(None, base)
    assert base[launch.FLEET_SHARE_ENV] == "0:0.25"  # input not mutated
    # composes with the topology rewrite (the Child launch env)
    env2 = launch.share_env("1:0.5", launch.topology_env(4, {"A": "b"}))
    assert env2[launch.FLEET_SHARE_ENV] == "1:0.5"
    assert "--xla_force_host_platform_device_count=4" in env2["XLA_FLAGS"]
    assert env2["A"] == "b"


# ----------------------------------------------- the loop (scripted children)


def write_scripted_trainer(tmp_path, exit_codes, run_dir_name="synthetic_0101_0000_run",
                           checkpoint=True):
    """A python child that logs its argv, creates a run dir (like a real
    driver's finalize) with a COMPLETE checkpoint inside (a real exit-75 /
    crash-after-save leaves one; ``checkpoint=False`` models dying before
    the first save), and exits per-invocation scripted codes — the
    test_launchers stub-python pattern."""
    script = tmp_path / "scripted_trainer.py"
    log = tmp_path / "calls.log"
    ws = tmp_path / "ws"
    script.write_text(f"""
import json, os, sys
log = {str(log)!r}
with open(log, "a") as f:
    f.write(json.dumps(sys.argv[1:]) + "\\n")
n = sum(1 for _ in open(log))
run_dir = os.path.join({str(ws)!r}, "synthetic_models", {run_dir_name!r})
os.makedirs(run_dir, exist_ok=True)
if {bool(checkpoint)!r}:
    ckpt = os.path.join(run_dir, "ckpt_epoch_1")
    os.makedirs(ckpt, exist_ok=True)
    with open(os.path.join(ckpt, "meta.json"), "w") as f:
        f.write('{{"epoch": 1}}')
codes = {list(exit_codes)!r}
sys.exit(codes[n - 1])
""")
    return script, log, ws


def run_supervisor(cfg):
    sup = Supervisor(cfg)
    rc = sup.run()
    return sup, rc


def read_events(sup):
    with open(os.path.join(sup.supervise_dir, "events.jsonl")) as f:
        return [json.loads(line) for line in f]


def test_loop_preempt_then_done_injects_resume_and_records(tmp_path):
    script, log, ws = write_scripted_trainer(tmp_path, [75, 0])
    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=3, poll_s=0.02, backoff_base_s=0.01,
    )
    sup, rc = run_supervisor(cfg)
    assert rc == 0
    assert [d.action for d in sup.decisions] == [policy.RESTART, policy.DONE]
    calls = [json.loads(line) for line in open(log)]
    assert "--resume" not in calls[0]
    assert calls[1][-2:] == [
        "--resume", os.path.join(str(ws), "synthetic_models",
                                 "synthetic_0101_0000_run"),
    ]
    names = [e["name"] for e in read_events(sup)]
    assert names.count("launch") == 2 and names.count("decision") == 2


def test_loop_gives_up_after_budget_with_shell_normalized_rc(tmp_path):
    script, log, ws = write_scripted_trainer(tmp_path, [1, 1])
    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=1, poll_s=0.02, backoff_base_s=0.01,
    )
    sup, rc = run_supervisor(cfg)
    assert rc == 1
    assert [d.action for d in sup.decisions] == [
        policy.BACKOFF_RESTART, policy.GIVE_UP,
    ]
    assert len([json.loads(line) for line in open(log)]) == 2


def test_loop_health_abort_gives_up_immediately(tmp_path):
    script, log, ws = write_scripted_trainer(tmp_path, [3])
    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=5, poll_s=0.02,
    )
    sup, rc = run_supervisor(cfg)
    assert rc == 3
    assert [d.action for d in sup.decisions] == [policy.GIVE_UP]
    assert len(list(open(log))) == 1  # no relaunch burned on a collapse


def test_loop_resize_request_preempts_and_relaunches_resized(tmp_path):
    """The elastic path with a scripted child: the child sleeps until the
    supervisor's resize-triggered SIGTERM (exiting 75 like the real
    preemption machinery), and the relaunch must carry the new topology in
    XLA_FLAGS plus --resume."""
    log = tmp_path / "calls.log"
    ws = tmp_path / "ws"
    script = tmp_path / "sleeper.py"
    script.write_text(f"""
import json, os, signal, sys, time
log = {str(log)!r}
with open(log, "a") as f:
    f.write(json.dumps([os.environ.get("XLA_FLAGS", "")] + sys.argv[1:]) + "\\n")
n = sum(1 for _ in open(log))
run_dir = os.path.join({str(ws)!r}, "synthetic_models", "synthetic_0101_0000_run")
ckpt = os.path.join(run_dir, "preempt_epoch_1_step_2")
os.makedirs(ckpt, exist_ok=True)
with open(os.path.join(ckpt, "meta.json"), "w") as f:
    f.write('{{"epoch": 0, "step_in_epoch": 2}}')
if n == 1:
    signal.signal(signal.SIGTERM, lambda *a: sys.exit(75))
    time.sleep(60)
sys.exit(0)
""")
    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=3, poll_s=0.05, grace_secs=10.0, devices=8,
    )
    sup = Supervisor(cfg)
    box = {}
    t = threading.Thread(target=lambda: box.update(rc=sup.run()), daemon=True)
    t.start()
    # wait for attempt 1, then file the resize request
    deadline = 50.0
    import time as _time

    while not log.exists() and deadline > 0:
        _time.sleep(0.05)
        deadline -= 0.05
    with open(os.path.join(sup.supervise_dir, "resize_request"), "w") as f:
        f.write("2")
    t.join(timeout=60)
    assert not t.is_alive() and box["rc"] == 0
    assert [d.action for d in sup.decisions] == [
        policy.RESTART_RESIZED, policy.DONE,
    ]
    calls = [json.loads(line) for line in open(log)]
    assert "--xla_force_host_platform_device_count=8" in calls[0][0]
    assert "--xla_force_host_platform_device_count=2" in calls[1][0]
    assert "--resume" in calls[1]
    events = read_events(sup)
    assert any(e["name"] == "resize_request" for e in events)
    resized = [e for e in events if e["name"] == "launch"][1]
    assert resized["args"]["devices"] == 2


@pytest.mark.chaos
def test_loop_straggler_mitigation_drives_the_full_ladder(tmp_path):
    """The LOOP end to end with a scripted fleet: a fake scraper keeps
    reporting host 1 as the boundary straggler, and the supervisor must
    walk the whole ladder — mitigation preempt -> restart_rebalanced with
    the FLEET_SHARE_HINT actually in the relaunch's environment ->
    (still slow) -> restart_resized excluding the host -> (still slow) ->
    give_up, exiting with the child's clean 75.

    The scraper serves gauges only once the CURRENT attempt's child has
    installed its SIGTERM handler (it logs after installing), so the
    graceful preempt is deterministic, not a boot race."""
    import time as _time

    log = tmp_path / "calls.log"
    ws = tmp_path / "ws"
    script = tmp_path / "fleet_stub.py"
    script.write_text(f"""
import json, os, signal, sys, time
signal.signal(signal.SIGTERM, lambda *a: sys.exit(75))
run_dir = os.path.join({str(ws)!r}, "synthetic_models", "synthetic_0101_0000_run")
ckpt = os.path.join(run_dir, "preempt_epoch_1_step_2")
os.makedirs(ckpt, exist_ok=True)
with open(os.path.join(ckpt, "meta.json"), "w") as f:
    f.write('{{"epoch": 1, "step_in_epoch": 2}}')
with open({str(log)!r}, "a") as f:
    f.write(json.dumps({{
        "share": os.environ.get("FLEET_SHARE_HINT", ""),
        "xla": os.environ.get("XLA_FLAGS", ""),
    }}) + "\\n")
time.sleep(60)
sys.exit(0)
""")

    class SkewScraper:
        """train_boundary_* gauges naming host 1, a fresh boundary per
        scrape — but only while the newest child is ready (handler
        installed == its log line written)."""

        sup = None

        def __init__(self):
            self.step = 0

        def scrape(self):
            try:
                with open(log) as f:
                    ready = sum(1 for _ in f)
            except OSError:
                ready = 0
            if self.sup is None or ready <= len(self.sup.decisions):
                return None  # current attempt's handler not installed yet
            self.step += 1
            return {
                "train_step": float(self.step),
                observe.SKEW_GAUGE: 0.2,
                observe.STRAGGLER_GAUGE: 1.0,
                observe.PROC_COUNT_GAUGE: 2.0,
            }

    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=10, poll_s=0.05, grace_secs=20.0,
        straggler_skew_secs=0.1, straggler_persist_k=3,
        straggler_window_n=5, straggler_mitigate=True,
    )
    scraper = SkewScraper()
    sup = Supervisor(cfg, scraper=scraper)
    scraper.sup = sup
    box = {}
    t = threading.Thread(target=lambda: box.update(rc=sup.run()), daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "mitigation ladder never completed"
    assert box["rc"] == 75  # give_up reports the final clean preempt code
    assert [d.action for d in sup.decisions] == [
        policy.RESTART_REBALANCED, policy.RESTART_RESIZED, policy.GIVE_UP,
    ]
    assert sup.decisions[0].share == "1:0.5"
    assert sup.decisions[1].devices == 1  # 2 processes minus the slow host

    calls = [json.loads(line) for line in open(log)]
    assert len(calls) == 3
    # the rebalance hint reached ONLY the rebalanced relaunch's environment
    assert [c["share"] for c in calls] == ["", "1:0.5", ""]
    # ...and the exclusion rung carried the shrunk topology
    assert "--xla_force_host_platform_device_count=1" in calls[2]["xla"]

    events = read_events(sup)
    names = [e["name"] for e in events]
    assert names.count("straggler_persistent") == 3
    mitigation = [e["args"] for e in events
                  if e["name"] == "straggler_mitigation"]
    assert [m["phase"] for m in mitigation] == [
        "preempt", "decided", "preempt", "decided", "preempt", "decided",
    ]
    assert [m.get("action") for m in mitigation if m["phase"] == "decided"] \
        == ["restart_rebalanced", "restart_resized", "give_up"]
    launches = [e["args"] for e in events if e["name"] == "launch"]
    assert [la.get("share") for la in launches] == [None, "1:0.5", None]
    # every relaunch resumed from the preempt save
    assert all(la["resume"] for la in launches[1:])


@pytest.mark.chaos
def test_loop_warn_only_records_verdicts_without_acting(tmp_path):
    """straggler_mitigate=False (the default): verdicts land on the
    recorder as straggler_persistent events, but the child is never
    preempted — the run completes and the decision log shows only DONE."""
    ws = tmp_path / "ws"
    script = tmp_path / "warn_stub.py"
    # lives long enough to be scraped a few times, then completes cleanly
    script.write_text(f"""
import os, sys, time
os.makedirs(os.path.join({str(ws)!r}, "synthetic_models", "r1"), exist_ok=True)
time.sleep(1.5)
sys.exit(0)
""")

    class OneShotSkew:
        def __init__(self):
            self.step = 0

        def scrape(self):
            self.step += 1
            return {
                "train_step": float(self.step),
                observe.SKEW_GAUGE: 0.2,
                observe.STRAGGLER_GAUGE: 1.0,
                observe.PROC_COUNT_GAUGE: 2.0,
            }

    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=3, poll_s=0.02, straggler_skew_secs=0.1,
        straggler_persist_k=1, straggler_window_n=1,
    )
    sup = Supervisor(cfg, scraper=OneShotSkew())
    rc = sup.run()
    assert rc == 0
    assert [d.action for d in sup.decisions] == [policy.DONE]
    events = read_events(sup)
    verdicts = [e["args"] for e in events
                if e["name"] == "straggler_persistent"]
    assert verdicts and all(v["mitigate"] is False for v in verdicts)
    assert not [e for e in events if e["name"] == "straggler_mitigation"]


# ------------------------------------------- committed evidence + ratchet gate


def _gate():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import ratchet

    return ratchet


def sample_matrix_artifact():
    return {
        "metric": "supervisor_matrix",
        "scenarios": {
            "sigkill": {"ok": True, "rc": 0,
                        "decisions": ["backoff_restart", "done"]},
            "stall": {"ok": True, "rc": 0,
                      "decisions": ["backoff_restart", "done"],
                      "liveness_stalls": 1, "watchdog_dumps_observed": 1},
            "collapse": {"ok": True, "rc": 3, "decisions": ["give_up"],
                         "health_alarms_observed": 1},
            "preempt_resize": {"ok": True, "rc": 0,
                               "decisions": ["restart_resized", "done"],
                               "launch_devices": [8, 4],
                               "resumed_resized": True},
        },
        "ok": True,
    }


def test_supervisor_gate_record_accepts_complete_matrix():
    r = _gate().supervisor_gate_record(sample_matrix_artifact())
    assert r["ok"], r
    assert r["metric"] == "ratchet_supervisor_matrix"
    assert sorted(r["scenarios"]) == [
        "collapse", "preempt_resize", "sigkill", "stall",
    ]


def test_supervisor_gate_record_rejects_missing_or_failed_scenarios():
    gate = _gate()
    art = sample_matrix_artifact()
    del art["scenarios"]["stall"]
    r = gate.supervisor_gate_record(art)
    assert not r["ok"] and "stall" in r["error"]

    art2 = sample_matrix_artifact()
    art2["scenarios"]["sigkill"]["ok"] = False
    r2 = gate.supervisor_gate_record(art2)
    assert not r2["ok"] and "sigkill" in r2["error"]

    # a resize leg that never actually changed topology must not pass
    art3 = sample_matrix_artifact()
    art3["scenarios"]["preempt_resize"]["resumed_resized"] = False
    r3 = gate.supervisor_gate_record(art3)
    assert not r3["ok"]


def test_committed_evidence_artifact_passes_the_gate():
    """docs/evidence/supervisor_r11.json — produced by
    scripts/supervisor_matrix.py driving the REAL supervisor over the real
    driver — must satisfy the same pure gate ratchet runs."""
    path = os.path.join(REPO, "docs", "evidence", "supervisor_r11.json")
    with open(path) as f:
        artifact = json.load(f)
    r = _gate().supervisor_gate_record(artifact)
    assert r["ok"], r


def sample_chaos_artifact():
    return {
        "metric": "chaos_matrix",
        "schema": "chaos_matrix/v1",
        "scenarios": {
            "straggler": {
                "ok": True, "rc": 0,
                "decisions": ["restart_rebalanced", "done"],
                "straggler_findings": 4, "persistence_verdicts": 1,
                "mitigation_events": 2,
                "launch_shares": [None, "1:0.5"],
                "share_hint_carried": "1:0.5",
                "digests": [12.5, 12.5], "control_digests": [12.5, 12.5],
                "bit_identical": True,
            },
            "chaos": {
                "ok": True, "rc": 0,
                "decisions": ["restart_rebalanced", "backoff_restart",
                              "done"],
                "mitigation_events": 2, "killed_pid": 4242,
                "health_alarms_observed": 6,
            },
        },
        "ok": True,
    }


@pytest.mark.chaos
def test_chaos_gate_record_accepts_complete_artifact():
    r = _gate().chaos_gate_record(sample_chaos_artifact())
    assert r["ok"], r
    assert r["metric"] == "ratchet_chaos_matrix"
    assert sorted(r["scenarios"]) == ["chaos", "straggler"]


@pytest.mark.chaos
def test_chaos_gate_record_rejects_weakened_evidence():
    """Each load-bearing claim, individually removed, must fail the gate —
    a hand-edited artifact cannot sneak past on decision strings alone."""
    gate = _gate()
    art = sample_chaos_artifact()
    art["schema"] = "chaos_matrix/v0"
    assert not gate.chaos_gate_record(art)["ok"]

    art = sample_chaos_artifact()
    del art["scenarios"]["chaos"]
    r = gate.chaos_gate_record(art)
    assert not r["ok"] and "chaos" in r["error"]

    art = sample_chaos_artifact()
    art["scenarios"]["straggler"]["decisions"] = ["backoff_restart", "done"]
    assert not gate.chaos_gate_record(art)["ok"]

    art = sample_chaos_artifact()
    art["scenarios"]["straggler"]["rc"] = 75
    assert not gate.chaos_gate_record(art)["ok"]

    # mitigation must have BOTH phases on record (preempt + decided)
    art = sample_chaos_artifact()
    art["scenarios"]["chaos"]["mitigation_events"] = 1
    assert not gate.chaos_gate_record(art)["ok"]

    # the share hint must have actually reached a relaunch
    art = sample_chaos_artifact()
    art["scenarios"]["straggler"]["launch_shares"] = [None, None]
    r = gate.chaos_gate_record(art)
    assert not r["ok"] and "share" in r["error"]

    # digest divergence from the policy-off control is disqualifying
    art = sample_chaos_artifact()
    art["scenarios"]["straggler"]["bit_identical"] = False
    r = gate.chaos_gate_record(art)
    assert not r["ok"] and "control" in r["error"]

    art = sample_chaos_artifact()
    art["scenarios"]["chaos"]["health_alarms_observed"] = 0
    assert not gate.chaos_gate_record(art)["ok"]

    art = sample_chaos_artifact()
    art["scenarios"]["chaos"]["killed_pid"] = 0
    assert not gate.chaos_gate_record(art)["ok"]


@pytest.mark.chaos
def test_committed_chaos_evidence_passes_the_gate():
    """docs/evidence/chaos_matrix_r16.json — produced by
    scripts/supervisor_matrix.py --scenarios straggler chaos driving the
    REAL supervisor over the real gloo fleet — must satisfy the same pure
    gate ratchet runs."""
    path = os.path.join(REPO, "docs", "evidence", "chaos_matrix_r16.json")
    with open(path) as f:
        artifact = json.load(f)
    r = _gate().chaos_gate_record(artifact)
    assert r["ok"], r


# ------------------------------------------------------- review-pinned fixes


def test_find_resume_dir_exclude_override_for_probe_and_ce(tmp_path):
    """A supervisor babysitting the probe/CE trainer passes exclude=() —
    their run dirs ARE the classifier_*/ce_* folders the pretrain default
    skips (without this the watch channel would be blind)."""
    ws = tmp_path / "ws"
    probe = ws / "cifar10_models" / "classifier_0101_0000_run"
    probe.mkdir(parents=True)
    assert launch.find_resume_dir(str(ws)) is None  # pretrain scan: excluded
    assert launch.find_resume_dir(str(ws), exclude=()) == str(probe)


def test_stale_stall_dump_from_previous_session_does_not_kill(tmp_path):
    """A stall dump left on disk by a PREVIOUS supervisor session must not
    liveness-kill a fresh healthy child: the verdict counts only dumps
    written during the current attempt (mtime), while the stale artifact
    is still recorded as an observation (fresh=false)."""
    script, log, ws = write_scripted_trainer(tmp_path, [0])
    run_dir = ws / "synthetic_models" / "synthetic_0101_0000_run"
    run_dir.mkdir(parents=True)
    dump = run_dir / "stall_dump_1.txt"
    dump.write_text("old stacks")
    os.utime(dump, (1000, 1000))  # long before this attempt
    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=3, poll_s=0.02, stall_secs=30.0, grace_secs=1.0,
    )
    sup, rc = run_supervisor(cfg)
    assert rc == 0
    assert [d.action for d in sup.decisions] == [policy.DONE]
    events = read_events(sup)
    assert not [e for e in events if e["name"] == "liveness_stall"]
    observed = [e for e in events if e["name"] == "stall_dump_observed"]
    assert observed and observed[0]["args"]["fresh"] is False


def test_resize_request_unreadable_is_retried_not_discarded(tmp_path):
    """A transient read failure must leave the operator's resize_request in
    place for the next poll (it is the only copy of the intent); only a
    successfully read file is consumed."""
    ws = tmp_path / "ws"
    cfg = SuperviseConfig(command=["true"], workdir=str(ws))
    sup = Supervisor(cfg)
    try:
        path = os.path.join(sup.supervise_dir, "resize_request")
        os.mkdir(path)  # open() -> IsADirectoryError, an OSError
        assert sup._resize_requested() is None
        assert os.path.exists(path)  # left for retry
        os.rmdir(path)
        # empty = caught mid-write (shell truncate-then-write): retried,
        # never deleted — a later poll sees the completed content
        with open(path, "w") as f:
            f.write("")
        assert sup._resize_requested() is None
        assert os.path.exists(path)
        with open(path, "w") as f:
            f.write("4")
        assert sup._resize_requested() == 4
        assert not os.path.exists(path)  # consumed exactly once
        # malformed CONTENT is genuinely bad: discarded with a warning
        with open(path, "w") as f:
            f.write("lots")
        assert sup._resize_requested() is None
        assert not os.path.exists(path)
    finally:
        sup.recorder.close()


def test_terminate_gracefully_honors_injected_clock(tmp_path):
    """The grace deadline runs on the injected clock (paired with the
    injected sleep): a fake pair must escalate to SIGKILL without
    real-time waiting or busy-spinning."""
    import subprocess
    import time as _time

    child = launch.Child([sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        # let the child boot so SIGTERM isn't delivered pre-main
        deadline = _time.time() + 10
        while child.poll() is None and _time.time() < deadline:
            break
        t = {"now": 0.0}
        sleeps = []

        def fake_sleep(s):
            sleeps.append(s)
            t["now"] += s
            _time.sleep(0.01)  # yield so the OS can reap the SIGKILL

        wall0 = _time.time()
        # python ignores nothing here: SIGTERM kills it quickly in reality,
        # so use a SIGTERM-absorbing child to force the escalation path
        child.proc.kill()
        child.proc.wait()
        absorbing = launch.Child([sys.executable, "-c", (
            "import signal, time\n"
            "signal.signal(signal.SIGTERM, lambda *a: None)\n"
            "print('ready', flush=True)\n"
            "time.sleep(60)\n"
        )])
        _time.sleep(1.0)  # crude boot wait: the handler must be installed
        rc = absorbing.terminate_gracefully(
            grace_s=3600.0, sleep=fake_sleep,
            clock=lambda: t["now"], poll_s=1.0,
        )
        assert rc == -9  # escalated to SIGKILL
        # the whole hour of grace elapsed on the FAKE clock, not the wall
        assert _time.time() - wall0 < 60
        assert len(sleeps) <= 3601
    finally:
        if child.poll() is None:
            child.proc.kill()


def test_policy_stall_kill_that_exits_75_is_not_a_clean_preemption():
    """A responsive-enough child turns the supervisor's stall SIGTERM into
    a tidy exit 75 — but the verdict that triggered the kill is still a
    failure: no streak reset, backoff applies, and the reason names the
    kill (not scheduler preemption), or a recurring borderline stall would
    hammer the restart budget in a tight kill/relaunch loop."""
    p = policy.DecisionPolicy(max_restarts=100, backoff_base_s=1.0)
    d1 = p.decide(obs(75, stalled=True, stall_dumps=1))
    assert d1.action == policy.BACKOFF_RESTART
    assert "stalled" in d1.reason and "state saved" in d1.reason
    assert d1.delay_s == 1.0
    d2 = p.decide(obs(75, stalled=True))
    assert d2.delay_s == 2.0  # the streak GREW across stall kills
    # a genuine preemption afterwards still resets cleanly
    assert p.decide(obs(75)).delay_s == 0.0
    assert p.decide(obs(-9)).delay_s == 1.0


def test_resume_injection_requires_a_complete_checkpoint(tmp_path):
    """A child that dies before its FIRST save leaves an empty newest run
    dir: injecting --resume there would fail the trainer's resume
    resolution on every retry (each failed attempt minting another empty
    decoy) until the budget burned. The supervisor must restart from
    scratch instead — and still resume once a complete save exists."""
    script, log, ws = write_scripted_trainer(tmp_path, [1, 1],
                                             checkpoint=False)
    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=1, poll_s=0.02, backoff_base_s=0.01,
    )
    sup, rc = run_supervisor(cfg)
    assert rc == 1
    calls = [json.loads(line) for line in open(log)]
    assert len(calls) == 2
    assert all("--resume" not in c for c in calls)  # scratch restarts

    # find_resume_dir itself: unfiltered newest for the WATCH channel,
    # checkpoint-bearing newest for the RESUME channel
    empty = ws / "synthetic_models" / "synthetic_0101_0000_run"
    complete = ws / "synthetic_models" / "synthetic_0001_0000_old"
    (complete / "ckpt_epoch_3").mkdir(parents=True)
    (complete / "ckpt_epoch_3" / "meta.json").write_text('{"epoch": 3}')
    os.utime(complete, (1000, 1000))  # older than the empty decoy
    assert launch.find_resume_dir(str(ws)) == str(empty)
    assert launch.find_resume_dir(
        str(ws), require_checkpoint=True
    ) == str(complete)


def test_resize_request_between_attempts_applies_at_launch(tmp_path):
    """A resize filed while NO child is running (during backoff, or while
    the supervisor itself was down) must apply directly to the next launch
    — routing it through the kill path would boot a child on the old
    topology only to preempt it immediately, burning one restart-budget
    unit and a full startup on a routine operator action."""
    script, log, ws = write_scripted_trainer(tmp_path, [0])
    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=3, poll_s=0.02, devices=8,
    )
    sup = Supervisor(cfg)
    os.makedirs(sup.supervise_dir, exist_ok=True)
    with open(os.path.join(sup.supervise_dir, "resize_request"), "w") as f:
        f.write("2")
    rc = sup.run()
    assert rc == 0
    assert [d.action for d in sup.decisions] == [policy.DONE]  # no budget burned
    events = read_events(sup)
    launches = [e["args"] for e in events if e["name"] == "launch"]
    assert len(launches) == 1 and launches[0]["devices"] == 2
    resize_evs = [e["args"] for e in events if e["name"] == "resize_request"]
    assert resize_evs == [{"devices": 2, "applied": "at_launch"}]


def test_watcher_reports_overwritten_stall_dump(tmp_path):
    """A relaunched trainer's watchdog restarts its counter and OVERWRITES
    stall_dump_1.txt in the reused run dir: path identity alone would hide
    every stall after the first (and, without a metrics scrape, leave the
    supervisor polling a wedged child forever) — a changed mtime makes the
    dump new again."""
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    w = observe.RunDirWatcher(str(run_dir))
    dump = run_dir / "stall_dump_1.txt"
    dump.write_text("attempt 1 stacks")
    os.utime(dump, (1000, 1000))
    assert len(w.poll()[0]) == 1
    assert w.poll()[0] == []  # unchanged: not re-reported
    dump.write_text("attempt 2 stacks")  # overwrite, fresh mtime
    assert len(w.poll()[0]) == 1
    assert w.poll()[0] == []


def test_unlaunchable_command_gives_up_with_recorded_decision(tmp_path):
    """A typo'd executable must end in a classified give_up (shell 127)
    with the failure on the recorder — not an unrecorded supervisor
    traceback (the delegated launcher path would otherwise surface a raw
    crash instead of a decision)."""
    ws = tmp_path / "ws"
    cfg = SuperviseConfig(
        command=["no-such-trainer-binary", "--flag"], workdir=str(ws),
        max_restarts=3, poll_s=0.02,
    )
    sup, rc = run_supervisor(cfg)
    assert rc == 127
    assert [d.action for d in sup.decisions] == [policy.GIVE_UP]
    assert "failed to launch" in sup.decisions[0].reason
    # past the process's start, with which every recorder opens
    events = [e for e in read_events(sup) if e["track"] != tracing.SETUP_TRACK]
    assert [e["name"] for e in events] == ["launch_failed", "decision"]
    assert events[1]["args"]["rc"] == 127


def test_supervisor_signal_relays_to_child_and_shuts_down(tmp_path):
    """When the SUPERVISOR is preempted (the launchers exec it, so it is
    what a fleet scheduler SIGTERMs), it must relay through the grace
    escalation — giving the trainer its emergency-save window — and exit
    with the child's code instead of relaunching. (Run off the main
    thread, the OS handler degrades; the flag path is driven directly.)"""
    log = tmp_path / "calls.log"
    ws = tmp_path / "ws"
    script = tmp_path / "graceful.py"
    script.write_text(f"""
import json, os, signal, sys, time
with open({str(log)!r}, "a") as f:
    f.write(json.dumps(sys.argv[1:]) + "\\n")
os.makedirs(os.path.join({str(ws)!r}, "synthetic_models", "r1"), exist_ok=True)
signal.signal(signal.SIGTERM, lambda *a: sys.exit(75))
time.sleep(60)
""")
    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=3, poll_s=0.05, grace_secs=20.0,
    )
    sup = Supervisor(cfg)
    box = {}
    t = threading.Thread(target=lambda: box.update(rc=sup.run()), daemon=True)
    t.start()
    import time as _time

    deadline = _time.time() + 50
    while not log.exists() and _time.time() < deadline:
        _time.sleep(0.05)
    _time.sleep(0.3)  # let the child install its SIGTERM handler
    sup._handle_signal(15, None)  # what the OS handler would do
    t.join(timeout=60)
    assert not t.is_alive() and box["rc"] == 75  # the child's saved-state code
    assert [d.action for d in sup.decisions] == [policy.SHUTDOWN]
    events = read_events(sup)
    assert any(e["name"] == "supervisor_signal" for e in events)
    assert len([e for e in events if e["name"] == "launch"]) == 1  # no relaunch


def test_terminal_exit_discards_stale_resize_request(tmp_path):
    """A resize_request racing the final child exit must not leak to the
    next, unrelated supervised run in the same workdir: terminal exits
    delete it (and record the discard). The race is made deterministic by
    having the CHILD file the request just before exiting 0 — _watch_child
    observes the exit before its resize poll, so the request is pending at
    the DONE decision."""
    ws = tmp_path / "ws"
    supervise_dir = ws / "supervise"
    script = tmp_path / "racer.py"
    script.write_text(f"""
import os, sys
os.makedirs(os.path.join({str(ws)!r}, "synthetic_models", "r1"), exist_ok=True)
os.makedirs({str(supervise_dir)!r}, exist_ok=True)
with open(os.path.join({str(supervise_dir)!r}, "resize_request"), "w") as f:
    f.write("4")
sys.exit(0)
""")
    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=3, poll_s=0.5,
    )
    sup, rc = run_supervisor(cfg)
    assert rc == 0
    assert [d.action for d in sup.decisions] == [policy.DONE]
    assert not os.path.exists(
        os.path.join(sup.supervise_dir, "resize_request"))
    assert any(e["name"] == "resize_request_discarded"
               for e in read_events(sup))


def test_signal_during_backoff_skips_relaunch(tmp_path):
    """A SIGTERM landing while the supervisor sleeps out a backoff must end
    the run WITHOUT booting another child (a fresh trainer would only be
    killed mid-startup, wasting the scheduler's grace window): the backoff
    sleep is chunked and the loop re-checks the flag before relaunching."""
    import time as _time

    script, log, ws = write_scripted_trainer(tmp_path, [1, 0])
    cfg = SuperviseConfig(
        command=[sys.executable, str(script)], workdir=str(ws),
        max_restarts=3, poll_s=0.05, backoff_base_s=30.0,  # a LONG backoff
    )
    sup = Supervisor(cfg)
    box = {}
    t = threading.Thread(target=lambda: box.update(rc=sup.run()), daemon=True)
    t.start()
    deadline = _time.time() + 50
    while len(sup.decisions) < 1 and _time.time() < deadline:
        _time.sleep(0.02)  # wait until attempt 1 crashed -> backoff begins
    sup._handle_signal(15, None)
    t.join(timeout=30)
    assert not t.is_alive(), "supervisor sat out the full 30s backoff"
    assert box["rc"] == 1  # the last child's code, not a fresh kill's
    assert [d.action for d in sup.decisions] == [
        policy.BACKOFF_RESTART, policy.SHUTDOWN,
    ]
    assert len(list(open(log))) == 1  # no second launch
