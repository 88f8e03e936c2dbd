"""The static invariant linter (simclr_pytorch_distributed_tpu/analysis/).

Two halves, mirroring docs/ANALYSIS.md:

- the KNOWN-BAD fixture corpus (tests/lint_fixtures/): one minimal
  reconstruction per rule — incl. the PR-1 donated-read and the
  split-verdict conditional collective — each asserted to fire exactly
  the expected findings (a rule that stops firing is a dead gate);
- the CLEAN-TREE contract: the full linter over the real package reports
  zero unallowlisted findings, every allowlist entry is used and carries
  a reason, and the committed evidence artifact still passes the pure
  ratchet lint_gate_record.

Everything here is stdlib-ast only — no jax, no driver runs.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
sys.path.insert(0, os.path.join(REPO, "scripts"))

from simclr_pytorch_distributed_tpu.analysis import (  # noqa: E402
    allowlist as allowlist_mod,
    build_output,
    run_lint,
    runner,
)
from simclr_pytorch_distributed_tpu.analysis import core  # noqa: E402
from simclr_pytorch_distributed_tpu.analysis import (  # noqa: E402
    rule_collectives,
    rule_donation,
    rule_hotloop,
    rule_registry,
)

pytestmark = pytest.mark.lint


def fixture(name: str) -> core.LintModule:
    return core.load_module(os.path.join(FIXTURES, name), repo_root=FIXTURES)


# -- known-bad corpus: each rule fires on its reconstruction --------------

def test_conditional_collective_fires_once():
    """The split-verdict shape: a collective only process 0 enters."""
    findings = rule_collectives.check_module(
        fixture("bad_conditional_collective.py")
    )
    assert [f.rule for f in findings] == [rule_collectives.RULE_CONDITIONAL]
    f = findings[0]
    assert "save_checkpoint" in f.why and f.file.endswith(
        "bad_conditional_collective.py"
    )
    assert f.allowlist_key.startswith(
        "collective-schedule:conditional:bad_conditional_collective.py:"
        "save_if_main"
    )


def test_early_exit_collective_fires_once():
    findings = rule_collectives.check_module(
        fixture("bad_early_exit_collective.py")
    )
    assert [f.rule for f in findings] == [rule_collectives.RULE_EARLY_EXIT]
    assert "drain_global" in findings[0].why


def test_swallowed_collective_fires_once():
    findings = rule_collectives.check_module(
        fixture("bad_swallowed_collective.py")
    )
    assert [f.rule for f in findings] == [rule_collectives.RULE_SWALLOWED]
    assert "OSError" in findings[0].why


def test_bypassable_reraise_still_swallows(tmp_path):
    """A top-level raise that a conditional return can bypass is NOT a
    re-raise guarantee — the host taking the bypass branch swallows while
    a peer re-raises (review-hardened case)."""
    src = (
        "def boundary(telemetry, ring, consume, step, can_recover, retry):\n"
        "    try:\n"
        "        telemetry.flush_boundary(ring, consume, step_hint=step)\n"
        "    except OSError:\n"
        "        if can_recover():\n"
        "            return retry()\n"
        "        raise\n"
        "\n"
        "def boundary_ok(telemetry, ring, consume, step, log):\n"
        "    try:\n"
        "        telemetry.flush_boundary(ring, consume, step_hint=step)\n"
        "    except OSError:\n"
        "        log('failed')\n"
        "        raise\n"
    )
    path = str(tmp_path / "_tmp_bypass.py")
    with open(path, "w") as f:
        f.write(src)
    findings = rule_collectives.check_module(
        core.load_module(path, repo_root=str(tmp_path))
    )
    # the bypassable handler fires; the unconditional re-raise does not
    assert [f.rule for f in findings] == [rule_collectives.RULE_SWALLOWED]
    assert "boundary" in findings[0].allowlist_key
    assert "boundary_ok" not in findings[0].allowlist_key


def test_loop_nested_bypass_still_swallows(tmp_path):
    """A return nested in a for/while before the raise bypasses it (the
    review-hardened compound-statement case); a loop-LOCAL break binds to
    that loop and is not a handler exit, so the trailing raise holds."""
    src = (
        "def retry_loop(telemetry, ring, consume, step, retries, retry):\n"
        "    try:\n"
        "        telemetry.flush_boundary(ring, consume, step_hint=step)\n"
        "    except OSError:\n"
        "        for r in retries:\n"
        "            return retry(r)\n"
        "        raise\n"
        "\n"
        "def scan_then_raise(telemetry, ring, consume, step, retries, ok):\n"
        "    try:\n"
        "        telemetry.flush_boundary(ring, consume, step_hint=step)\n"
        "    except OSError:\n"
        "        for r in retries:\n"
        "            if ok(r):\n"
        "                break\n"
        "        raise\n"
    )
    path = str(tmp_path / "_tmp_loop_bypass.py")
    with open(path, "w") as f:
        f.write(src)
    findings = rule_collectives.check_module(
        core.load_module(path, repo_root=str(tmp_path))
    )
    assert [f.rule for f in findings] == [rule_collectives.RULE_SWALLOWED]
    assert "retry_loop" in findings[0].allowlist_key
    assert "scan_then_raise" not in findings[0].allowlist_key


def test_donated_read_fires_once():
    """The PR-1 reconstruction: the crash handler reads the donated state."""
    findings = rule_donation.check_module(fixture("bad_donated_read.py"))
    assert [f.rule for f in findings] == [rule_donation.RULE]
    f = findings[0]
    assert "'state'" in f.why and "donated" in f.why
    # the finding anchors on the post-donation READ, not the call
    assert f.line > 0


def test_donation_loop_without_rebind_fires(tmp_path):
    """A loop that re-dispatches the same donated object every iteration."""
    src = (
        "def run(update_fn, state, images, key):\n"
        "    for _ in range(3):\n"
        "        update_fn(state, images, key)\n"
    )
    path = str(tmp_path / "_tmp_loop.py")
    with open(path, "w") as f:
        f.write(src)
    findings = rule_donation.check_module(
        core.load_module(path, repo_root=str(tmp_path))
    )
    assert [f.rule for f in findings] == [rule_donation.RULE]
    assert "loop" in findings[0].why


def test_hotloop_sync_and_bare_annotation_fire():
    """float() in the boundary loop fires; the reasoned sync-ok site is
    suppressed; the bare marker fires the missing-reason rule."""
    findings = rule_hotloop.check_module(fixture("bad_hotloop_sync.py"))
    rules = sorted(f.rule for f in findings)
    assert rules == sorted([
        rule_hotloop.RULE_LOOP, rule_hotloop.RULE_ANNOTATION,
    ])
    loop_f = next(f for f in findings if f.rule == rule_hotloop.RULE_LOOP)
    assert "float()" in loop_f.why


def test_hotloop_jit_fires_once():
    findings = rule_hotloop.check_module(fixture("bad_hotloop_jit.py"))
    assert [f.rule for f in findings] == [rule_hotloop.RULE_JIT]
    assert "np.asarray" in findings[0].why


def test_pallas_kernel_sync_fires_once():
    """np.asarray inside a kernel handed to pallas_call via the
    intermediate-partial shape fires the pallas-kernel region; the clean
    kernel beside it stays silent."""
    findings = rule_hotloop.check_module(fixture("bad_pallas_kernel_sync.py"))
    assert [f.rule for f in findings] == [rule_hotloop.RULE_KERNEL]
    assert "_bad_kernel" in findings[0].why
    assert "Pallas kernel builder" in findings[0].why


def test_real_pallas_kernel_modules_are_clean():
    """The kernel modules a benchmark cell runs (ops/pallas_loss.py,
    ops/pointwise_bwd.py, ops/sparse_attention.py) pass the extended
    hot-loop rule: their kernel builders contain no sync-forcing host ops."""
    pkg = os.path.join(REPO, "simclr_pytorch_distributed_tpu", "ops")
    expected = {
        # every kernel builder must be under coverage — the builders all
        # reuse the local name 'kernel =' for their partial, so a
        # last-binding-wins resolution would silently drop most of them
        "pallas_loss.py": {"_fwd_kernel", "_bwd_kernel"},
        "pointwise_bwd.py": {"_kernel"},
        "sparse_attention.py": {"_fwd_kernel", "_bwd_kernel"},
    }
    for name, want in expected.items():
        mod = core.load_module(os.path.join(pkg, name), repo_root=REPO)
        kernels = {f.name for f in rule_hotloop._pallas_kernel_functions(mod)}
        assert want <= kernels, f"{name}: {want - kernels} not covered"
        assert rule_hotloop.check_module(mod) == []


def test_metric_keys_unsorted_fires_once():
    findings = rule_registry.check_metric_keys([fixture("bad_metric_keys.py")])
    assert [f.rule for f in findings] == [rule_registry.RULE_KEYS_SORTED]
    assert "FIXTURE_METRIC_KEYS" in findings[0].why


def test_metric_keys_multi_source_fires_once():
    findings = rule_registry.check_metric_keys([
        fixture("bad_metric_keys_copy.py"), fixture("bad_metric_keys_dup.py"),
    ])
    assert [f.rule for f in findings] == [rule_registry.RULE_KEYS_DUP]
    assert "FIXTURE_DUP_METRIC_KEYS" in findings[0].why


def test_schema_literal_fires_once():
    mod = core.load_module(
        os.path.join(FIXTURES, "scripts", "bad_schema_literal.py"),
        repo_root=FIXTURES,
    )
    assert mod.rel == "scripts/bad_schema_literal.py"
    findings = rule_registry.check_schema_stamps([mod])
    assert [f.rule for f in findings] == [rule_registry.RULE_SCHEMA]


def test_flag_type_mismatch_fires_once():
    findings = rule_registry.check_parser_flags(fixture("bad_flag_type.py"))
    assert [f.rule for f in findings] == [rule_registry.RULE_FLAG_TYPE]
    assert "--print_freq" in findings[0].why


def test_shared_flag_inline_fires_once():
    findings = rule_registry.check_parser_flags(fixture("bad_flag_inline.py"))
    assert [f.rule for f in findings] == [rule_registry.RULE_FLAG_INLINE]
    assert "--telemetry" in findings[0].why


def test_shared_flag_default_mismatch_fires_once():
    findings = rule_registry.check_parser_flags(
        fixture("bad_flag_default.py")
    )
    assert [f.rule for f in findings] == [rule_registry.RULE_FLAG_DEFAULT]
    assert "--telemetry" in findings[0].why


def test_rebound_donation_is_clean(tmp_path):
    """The canonical `state, ring = update_fn(state, ring, ...)` rotation
    must NOT fire — it is the whole tree's correct shape."""
    src = (
        "def run(update_fn, state, ring, batches, key):\n"
        "    for images, labels in batches:\n"
        "        state, ring = update_fn(state, ring, images, labels, key)\n"
        "    return state\n"
    )
    path = str(tmp_path / "_tmp_clean.py")
    with open(path, "w") as f:
        f.write(src)
    findings = rule_donation.check_module(
        core.load_module(path, repo_root=str(tmp_path))
    )
    assert findings == []


def test_uniform_conditionals_are_clean(tmp_path):
    """process_count short-circuits and epoch-uniform tests are the repo's
    standard shapes — not hazards."""
    src = (
        "def boundary(telemetry, jax, epoch, save_freq, step):\n"
        "    if jax.process_count() == 1:\n"
        "        return\n"
        "    telemetry.check_failures_global(step)\n"
        "    if epoch % save_freq == 0:\n"
        "        telemetry.drain_global(step)\n"
    )
    path = str(tmp_path / "_tmp_uniform.py")
    with open(path, "w") as f:
        f.write(src)
    findings = rule_collectives.check_module(
        core.load_module(path, repo_root=str(tmp_path))
    )
    assert findings == []


# -- the clean-tree contract ---------------------------------------------

def test_clean_tree_no_unallowlisted_findings():
    """The full linter over the real tree: zero findings, and every
    allowlist entry both used and reasoned (stale entries are findings,
    so this also pins allowlist hygiene)."""
    result = run_lint(REPO)
    assert result["findings"] == [], "\n".join(
        f.render() for f in result["findings"]
    )
    assert result["rules_run"] == list(runner.RULE_FAMILIES)
    assert result["files_scanned"] > 50  # the whole tree, not a subset
    # the one designed matched point (train/supcon.py NaN rollback) matched
    assert all(a["findings"] for a in result["allowlisted"])


def test_allowlist_entries_carry_reasons():
    allowlist_mod.validate()  # must not raise on the committed allowlist
    with pytest.raises(ValueError, match="no reason"):
        run_lint(REPO, allowlist={"some:key": "  "})


def test_stale_allowlist_entry_is_a_finding():
    result = run_lint(REPO, allowlist={"bogus:key:never:matches": "reason"})
    stale = [f for f in result["findings"]
             if f.rule == runner.RULE_STALE]
    assert len(stale) == 1 and "bogus:key:never:matches" in stale[0].why


def test_analysis_package_is_stdlib_only():
    """The linter must run without jax: no analysis module may import
    jax/numpy/flax (the package PARENT's convenience re-export is outside
    this contract and documented in docs/ANALYSIS.md)."""
    import ast as ast_mod

    adir = os.path.join(REPO, "simclr_pytorch_distributed_tpu", "analysis")
    banned = {"jax", "numpy", "np", "flax", "optax", "orbax"}
    for fn in sorted(os.listdir(adir)):
        if not fn.endswith(".py"):
            continue
        with open(os.path.join(adir, fn)) as f:
            tree = ast_mod.parse(f.read())
        for node in ast_mod.walk(tree):
            mods = []
            if isinstance(node, ast_mod.Import):
                mods = [a.name.split(".")[0] for a in node.names]
            elif isinstance(node, ast_mod.ImportFrom) and node.module:
                mods = [node.module.split(".")[0]]
            assert not (set(mods) & banned), f"{fn} imports {mods}"


# -- artifact, CLI, and the ratchet gate ----------------------------------

def test_build_output_schema_pinned():
    out = build_output(run_lint(REPO))
    assert out["schema"] == runner.SCHEMA == "invariant_lint/v1"
    assert out["ok"] is True and out["n_findings"] == 0
    assert set(out) == {
        "schema", "ok", "n_findings", "findings", "allowlisted",
        "files_scanned", "rules_run",
    }
    json.dumps(out)  # JSON-safe


def test_cli_runs_without_jax(tmp_path):
    """The linter's whole point is running anywhere instantly: the CLI
    must work on a box with NO jax (the package parent's re-export is
    lazy, PEP 562). A meta-path blocker makes any jax/flax/optax/orbax
    import raise — the CLI must still lint the tree and exit 0."""
    blocker = tmp_path / "noheavy.py"
    blocker.write_text(
        "import sys\n"
        "class _Block:\n"
        "    BANNED = {'jax', 'jaxlib', 'flax', 'optax', 'orbax'}\n"
        "    def find_spec(self, name, path=None, target=None):\n"
        "        if name.split('.')[0] in self.BANNED:\n"
        "            raise ImportError(f'{name} blocked for the jax-free "
        "lint contract')\n"
        "        return None\n"
        "sys.meta_path.insert(0, _Block())\n"
        "import runpy\n"
        "sys.argv = sys.argv[1:]\n"
        "runpy.run_path(sys.argv[0], run_name='__main__')\n"
    )
    proc = subprocess.run(
        [sys.executable, str(blocker),
         os.path.join(REPO, "scripts", "invariant_lint.py")],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "invariant_lint: 0 finding(s)" in proc.stdout


def test_cli_exits_zero_and_writes_artifact(tmp_path):
    out_json = tmp_path / "lint.json"
    proc = subprocess.run(
        [sys.executable, "scripts/invariant_lint.py", "--json",
         str(out_json)],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    with open(out_json) as f:
        artifact = json.load(f)
    assert artifact["ok"] is True
    assert "invariant_lint: 0 finding(s)" in proc.stdout


def _ratchet():
    spec = importlib.util.spec_from_file_location(
        "ratchet", os.path.join(REPO, "scripts", "ratchet.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lint_gate_record_pass_fail_matrix():
    ratchet = _ratchet()
    good = build_output(run_lint(REPO))
    rec = ratchet.lint_gate_record(good)
    assert rec["ok"] is True and rec["metric"] == "ratchet_invariant_lint"

    bad_schema = dict(good, schema="nope/v1")
    assert ratchet.lint_gate_record(bad_schema)["ok"] is False

    missing_rule = dict(good, rules_run=good["rules_run"][:-1])
    rec = ratchet.lint_gate_record(missing_rule)
    assert rec["ok"] is False and "did not run" in rec["error"]

    with_finding = dict(
        good, ok=False, n_findings=1,
        findings=[{"rule": "donation-safety:post-donation-read",
                   "file": "x.py", "line": 3, "why": "w",
                   "allowlist_key": "k"}],
    )
    rec = ratchet.lint_gate_record(with_finding)
    assert rec["ok"] is False and "x.py:3" in rec["error"]

    no_reason = dict(
        good,
        allowlisted=[{"key": "k", "reason": " ", "findings": [{}]}],
    )
    rec = ratchet.lint_gate_record(no_reason)
    assert rec["ok"] is False and "no reason" in rec["error"]


def test_ratchet_default_list_includes_lint_gate():
    ratchet = _ratchet()
    assert "invariant_lint" in ratchet.CONFIGS
    assert ratchet.CONFIGS["invariant_lint"]["kind"] == "invariant_lint"


def test_committed_evidence_passes_gate():
    """The committed docs/evidence artifact re-verifies under the pure
    gate record — the acceptance-criteria bind."""
    # regenerated in place whenever the scanned surface or the allowlist
    # moves (last: PR 30, after the fused-conv ladder's files went)
    path = os.path.join(REPO, "docs", "evidence", "invariant_lint_r19.json")
    with open(path) as f:
        artifact = json.load(f)
    ratchet = _ratchet()
    rec = ratchet.lint_gate_record(artifact)
    assert rec["ok"] is True, rec
    # the artifact reflects the current allowlist (no silent drift): same
    # keys as a fresh run
    fresh = build_output(run_lint(REPO))
    assert (
        [a["key"] for a in artifact["allowlisted"]]
        == [a["key"] for a in fresh["allowlisted"]]
    )
