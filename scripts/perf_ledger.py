#!/usr/bin/env python
"""The longitudinal perf ledger: every bench result, appended forever.

``bench.py`` measures one number per run and ``vs_baseline`` compares it
against ONE frozen headline — there is no history, so a slow drift (1% a
revision for ten revisions) is invisible to the gate until it crosses the
single 95% bar, and when it does there is nothing to bisect against. This
module is the history: ``docs/perf_ledger.jsonl`` holds one schema-pinned
record per bench run — git revision, a workload FINGERPRINT (stage,
config string, global batch, device kind, chips — the identity under
which throughput numbers are comparable at all), imgs/s/chip, step ms,
the clock-suspect verdict, and optionally the trace-report phase shares —
so perf drift becomes attributable to a REVISION (which commit moved the
number) and a PHASE (which part of the step absorbed the time).

Regression detection (:func:`detect_regression`, pure) follows the bench
gate's conventions: the latest record of each fingerprint group is
compared against the MEDIAN of its trailing same-fingerprint window;
clock-suspect runs are excluded from BOTH sides (a glitched number must
neither set nor trip the bar); groups without a sufficient clean trailing
window pass-skip with the reason on record (a new workload/device has no
history to regress against). ``scripts/ratchet.py``'s ``perf_ledger``
config runs the same pure verdict over the committed ledger.

Usage:
    python bench.py --ledger                       # measure + append
    python scripts/perf_ledger.py append --bench-json bench.log \
        [--phases trace_report.json] [--ledger docs/perf_ledger.jsonl]
    python scripts/perf_ledger.py check [--ledger docs/perf_ledger.jsonl] \
        [--json out.json]
"""

import argparse
import hashlib
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEMA = "perf_ledger/v1"
CHECK_SCHEMA = "perf_ledger_check/v1"  # the `check` CLI's own artifact
DEFAULT_LEDGER = os.path.join("docs", "perf_ledger.jsonl")
# every record must carry these (the pinned schema the ratchet gate checks)
REQUIRED_KEYS = (
    "schema", "ts_unix", "git_rev", "fingerprint", "stage", "device_kind",
    "chips", "imgs_per_sec_per_chip", "step_ms", "clock_suspect",
)
# regression bar: latest vs the trailing-window median, the ratchet bench
# gate's fraction (a ledger regression should fail exactly where the bench
# bar would, just against the measured history instead of one frozen number)
REGRESSION_FRACTION = 0.95
TRAIL_WINDOW = 5      # trailing same-fingerprint records consulted
MIN_TRAIL = 2         # fewer than this and the bar cannot bind


def git_rev(repo=REPO):
    """Short HEAD revision (+ '-dirty' when the tree is modified), or
    'unknown' outside a usable git checkout — a ledger record must never
    fail to append over provenance."""
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=repo,
            capture_output=True, text=True, timeout=10,
        )
        if rev.returncode != 0:
            return "unknown"
        out = rev.stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=repo,
            capture_output=True, text=True, timeout=10,
        )
        if dirty.returncode == 0 and dirty.stdout.strip():
            out += "-dirty"
        return out
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def fingerprint_for(stage, detail):
    """The workload identity under which throughput is comparable: stage +
    bench config string + global batch + device kind + chips (pure)."""
    ident = {
        "stage": stage,
        "config": detail.get("config"),
        "global_batch": detail.get("global_batch"),
        "device_kind": detail.get("device_kind"),
        "chips": detail.get("chips"),
    }
    blob = json.dumps(ident, sort_keys=True).encode()
    return hashlib.sha1(blob).hexdigest()[:12]


def phase_shares_from_artifact(artifact):
    """``{phase: share}`` (steady_state included) from a trace_report
    artifact — the per-phase attribution that makes a ledger regression
    assignable to a phase, not just a revision."""
    rep = artifact.get("report", {})
    shares = {
        name: p.get("share") for name, p in rep.get("phases", {}).items()
    }
    steady = rep.get("steady_state", {})
    if "share" in steady:
        shares["steady_state"] = steady["share"]
    return shares


def record_from_bench(bench_record, git_revision, ts_unix,
                      phase_shares=None, note=""):
    """One schema-pinned ledger record from bench.py's headline JSON
    (pure; tests pin the shape)."""
    detail = bench_record.get("detail", {})
    metric = bench_record.get("metric", "")
    stage = metric.split("_imgs_per_sec")[0] or "unknown"
    rec = {
        "schema": SCHEMA,
        "ts_unix": round(float(ts_unix), 3),
        "git_rev": git_revision,
        "fingerprint": fingerprint_for(stage, detail),
        "stage": stage,
        "device_kind": detail.get("device_kind"),
        "chips": detail.get("chips"),
        "imgs_per_sec_per_chip": float(bench_record["value"]),
        "step_ms": detail.get("step_ms"),
        "clock_suspect": bool(detail.get("clock_suspect")),
        "vs_baseline": bench_record.get("vs_baseline"),
        "config": detail.get("config"),
    }
    if phase_shares:
        rec["phase_shares"] = phase_shares
    if note:
        rec["note"] = note
    return rec


CORRUPT_LINE_SCHEMA = "__corrupt_line__"


def load_ledger(path):
    """All ledger records. Tolerates ONLY a torn FINAL line (an append
    racing this reader, or a killed bench mid-write). A COMPLETE line
    that fails to parse becomes a sentinel record (schema
    :data:`CORRUPT_LINE_SCHEMA`) so :func:`schema_errors` flags it — the
    gate must refuse a history it cannot fully interpret, not silently
    judge the surviving records (a vanished newest record would make the
    previous one 'latest' and the scan blind to the regression)."""
    if not os.path.exists(path):
        return []
    with open(path) as f:
        text = f.read()
    consumed = text.rfind("\n") + 1
    records = []
    for i, line in enumerate(text[:consumed].splitlines()):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            rec = None
        if not isinstance(rec, dict):
            rec = {"schema": CORRUPT_LINE_SCHEMA, "line": i + 1}
        records.append(rec)
    return records


def append_record(path, record):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(record, sort_keys=True) + "\n")


def schema_errors(records):
    """Per-record schema violations (pure): the gate refuses a ledger it
    cannot interpret rather than skipping silently."""
    errors = []
    for i, rec in enumerate(records):
        if rec.get("schema") == CORRUPT_LINE_SCHEMA:
            errors.append(
                f"record {i}: unparseable ledger line {rec.get('line')}"
            )
            continue
        if rec.get("schema") != SCHEMA:
            errors.append(f"record {i}: schema {rec.get('schema')!r}")
            continue
        missing = [k for k in REQUIRED_KEYS if k not in rec]
        if missing:
            errors.append(f"record {i}: missing keys {missing}")
    return errors


def _phase_suspect(latest, trail):
    """The phase whose share grew most vs the trailing record that carries
    shares — the 'look here first' pointer next to a regression verdict."""
    ref = next(
        (r for r in reversed(trail) if r.get("phase_shares")), None
    )
    shares = latest.get("phase_shares")
    if not shares or ref is None:
        return None
    deltas = {
        name: shares[name] - ref["phase_shares"].get(name, 0.0)
        for name in shares
    }
    name, delta = max(deltas.items(), key=lambda kv: kv[1])
    if delta <= 0:
        return None
    return {"phase": name, "share_delta": round(delta, 4)}


def detect_regression(records, fraction=REGRESSION_FRACTION,
                      window=TRAIL_WINDOW, min_trail=MIN_TRAIL):
    """Per-fingerprint regression verdicts for the LATEST record of each
    group (pure). Clock-suspect runs are excluded both as the subject and
    as window members (the bench-gate convention). Returns
    ``{fingerprint: {"status": "ok"|"regression"|"skipped", ...}}``."""
    groups = {}
    for rec in records:
        groups.setdefault(rec["fingerprint"], []).append(rec)
    verdicts = {}
    for fp, group in groups.items():
        clean = [r for r in group if not r.get("clock_suspect")]
        label = {
            "stage": group[-1].get("stage"),
            "device_kind": group[-1].get("device_kind"),
            "chips": group[-1].get("chips"),
        }
        if not clean:
            verdicts[fp] = dict(
                label, status="skipped",
                reason="every run in the group is clock-suspect",
            )
            continue
        latest = clean[-1]
        trail = clean[:-1][-window:]
        if len(trail) < min_trail:
            verdicts[fp] = dict(
                label, status="skipped",
                value=latest["imgs_per_sec_per_chip"],
                reason=f"trailing clean window {len(trail)} < {min_trail}: "
                       "no history to regress against",
            )
            continue
        baseline = statistics.median(
            r["imgs_per_sec_per_chip"] for r in trail
        )
        value = latest["imgs_per_sec_per_chip"]
        ratio = value / baseline if baseline > 0 else 0.0
        verdict = dict(
            label,
            status="regression" if ratio < fraction else "ok",
            value=value,
            baseline_median=round(baseline, 1),
            ratio=round(ratio, 4),
            window=len(trail),
            latest_rev=latest.get("git_rev"),
            window_revs=[r.get("git_rev") for r in trail],
        )
        if verdict["status"] == "regression":
            suspect = _phase_suspect(latest, trail)
            if suspect:
                verdict["phase_suspect"] = suspect
        verdicts[fp] = verdict
    return verdicts


def build_check_output(ledger_path, records, verdicts):
    """The check artifact (pure; schema pinned by tests)."""
    return {
        "schema": CHECK_SCHEMA,
        "ledger": ledger_path,
        "n_records": len(records),
        "schema_errors": schema_errors(records),
        "verdicts": verdicts,
        "ok": bool(records) and not schema_errors(records) and not any(
            v["status"] == "regression" for v in verdicts.values()
        ),
    }


def parse_bench_json(path):
    """bench.py's headline record from a captured stdout/log file: the
    LAST parseable JSON line carrying a 'metric' key (warmup/progress
    noise above it is ignored), or None. THE one copy of the bench-stdout
    parsing contract — scripts/ratchet.py wraps this with its own error
    type."""
    record = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict) and "metric" in obj:
                record = obj
    return record


def _parse_bench_json(path):
    record = parse_bench_json(path)
    if record is None:
        raise SystemExit(f"no bench JSON record in {path}")
    return record


def append_from_bench(ledger_path, bench_record, phases_path="", note=""):
    """Build + append one record from a bench headline dict (what
    ``bench.py --ledger`` calls); returns the appended record."""
    shares = None
    if phases_path:
        with open(phases_path) as f:
            shares = phase_shares_from_artifact(json.load(f))
    rec = record_from_bench(
        bench_record, git_rev(), time.time(), phase_shares=shares, note=note
    )
    append_record(ledger_path, rec)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_app = sub.add_parser("append", help="append one bench result")
    p_app.add_argument("--bench-json", required=True,
                       help="file holding bench.py's stdout (the last JSON "
                            "'metric' line is the record)")
    p_app.add_argument("--ledger", default=os.path.join(REPO, DEFAULT_LEDGER))
    p_app.add_argument("--phases", default="",
                       help="a trace_report artifact whose phase shares "
                            "ride the record")
    p_app.add_argument("--note", default="")
    p_chk = sub.add_parser("check", help="regression scan over the ledger")
    p_chk.add_argument("--ledger", default=os.path.join(REPO, DEFAULT_LEDGER))
    p_chk.add_argument("--json", default="",
                       help="write the check artifact here")
    args = ap.parse_args(argv)

    if args.cmd == "append":
        rec = append_from_bench(
            args.ledger, _parse_bench_json(args.bench_json),
            phases_path=args.phases, note=args.note,
        )
        print(json.dumps(rec, sort_keys=True))
        return 0

    records = load_ledger(args.ledger)
    # schema first: detect_regression indexes the pinned keys, so a
    # malformed record must surface as a schema error, not a KeyError
    verdicts = {} if schema_errors(records) else detect_regression(records)
    out = build_check_output(args.ledger, records, verdicts)
    for fp, v in sorted(verdicts.items()):
        print(json.dumps({"fingerprint": fp, **v}, sort_keys=True))
    for err in out["schema_errors"]:
        print(f"SCHEMA ERROR: {err}")
    print(json.dumps({
        "metric": "perf_ledger_check", "ok": out["ok"],
        "records": out["n_records"], "groups": len(verdicts),
    }))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
