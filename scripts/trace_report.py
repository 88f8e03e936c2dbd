#!/usr/bin/env python
"""Per-phase step-time attribution from a flight-recorder ``events.jsonl``.

The recorder (utils/tracing.py) instruments only host-visible boundaries,
and its ``main:*`` phase tracks never nest across each other — so summing
their span durations partitions the run's measured wall clock exactly:

    wall = compile + data + flush + checkpoint + collective + ...
           + steady_state (the remainder: the dispatch-only hot loop)

The remainder is split once more, by the counters the hot loop keeps without
recording (each ``flush_boundary`` span carries its window's ``dispatch_s``):
``dispatch`` is the time inside the update calls, mostly the host waiting for
room in the device's queue, and ``rest`` is everything else no span covers.
The epoch-end ``drain_wait`` spans are on ``main:flush`` and count there.

The attribution starts at the recorder's own start, and leaves out the
records from before it (``ts < 0``: the process's start, imports, set-up
spans and compiles made before the recorder existed). Set-up has a table
of its own: from the process's start to the first ``flush_boundary``, in
rows that never share a second (``build_setup``), and the ten programs with
the most compile seconds, each with its persistent-cache hits.

This script reads the jsonl, builds that attribution table with anomaly
flags (compile-dominated runs, flush-heavy windows, data stalls, recorded
stall/rollback/preemption events), prints it, and writes a JSON artifact —
the committed ``docs/evidence/trace_report_r*.json`` convention, and the
``trace_report`` config in ``scripts/ratchet.py``'s default gate list
(which binds on the attribution's internal consistency: phases
non-negative and non-overlapping, the table summing to the wall time).

``--fleet <run_dir>`` is the MULTI-PROCESS view: a pod writes one
``events_pN.jsonl`` per process on unaligned per-host monotonic clocks.
This mode discovers every session's per-process files, aligns the
timelines through the ``clock_anchor`` events each process stamps at
already-matched collective points (affine fit per process, residual
reported — utils/tracing.py), and emits: a merged Chrome trace (``pid`` =
process index), a per-collective skew table naming the straggler process
at each boundary (arrival = the ``main:collective`` span's start), a
straggler ranking, and per-process attribution consistency checks — all
through the pure ``build_fleet_report`` (the committed
``docs/evidence/fleet_report_r*.json`` convention, gate-verified by
ratchet's ``fleet_report`` config).

Usage:
    python scripts/trace_report.py --events <run_dir>/events.jsonl \
        [--json out.json]
    python scripts/trace_report.py --fleet <run_dir> \
        [--json out.json] [--trace merged_trace.json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from simclr_pytorch_distributed_tpu.utils import tracing  # noqa: E402
from simclr_pytorch_distributed_tpu.utils.tracing import (  # noqa: E402
    ANCHOR_EVENT,
    EPOCH_TRACK,
    MAIN_TRACK_PREFIX,
    chrome_trace_from_events,
)

SCHEMA = "trace_report/v1"
FLEET_SCHEMA = "fleet_report/v1"
COLLECTIVE_TRACK = "main:collective"
# max acceptable affine-fit residual: the anchors are post-release stamps
# of one physical instant, so after the per-process affine map they must
# agree to within collective release jitter (ms-scale even on a loaded
# CPU host; a residual past this means the merge cannot be trusted)
FLEET_RESIDUAL_TOL_S = 0.25

# advisory share thresholds per phase (fraction of wall): above them the
# phase is flagged — not an error, a "look here first" pointer
ANOMALY_SHARES = {
    "compile": 0.50,   # cold compile dominating: check the compile cache
    "data": 0.35,      # window staging not hidden by prefetch
    "flush": 0.25,     # telemetry flush on the critical path: check async
    "checkpoint": 0.25,  # save serialization/commit stalling the loop
    "eval": 0.60,      # validation dwarfing training (tiny-epoch smokes)
}
# recorded events that are findings in themselves
EVENT_FLAGS = {
    "stall_detected": "stall watchdog fired (see stall_dump_* artifacts)",
    "nan_rollback": "NaN rollback(s) recorded",
    "preempt_exit": "run ended by preemption",
    "flush_failure": "telemetry flush failure observed",
    "recorder_dropped": "flight-recorder ring saturated: trace.json and "
                        "watchdog snapshots truncated (events.jsonl is "
                        "complete)",
}
# span overlap tolerance (s): clock reads bracketing a record are not atomic
OVERLAP_TOL_S = 1e-4


def load_events(path):
    """One session's records — the shared torn-line-tolerant loader
    (tracing.parse_jsonl): the half-written final line a SIGKILL leaves
    behind is exactly the run this report exists to diagnose."""
    return tracing.load_events_jsonl(path)


def _attributed_tracks(events):
    tracks = {}
    for e in events:
        track = e.get("track", "")
        if (
            e.get("ph") == "X"
            and track.startswith(MAIN_TRACK_PREFIX)
            and track != EPOCH_TRACK
        ):
            tracks.setdefault(track, []).append(e)
    return tracks


def build_report(events):
    """The attribution report (pure — tests/test_scripts.py drives it on
    synthetic event lists)."""
    if not events:
        raise ValueError("no events: recorder off or empty run?")
    # from the recorder's own start: what came before it (ts < 0: the
    # process's start, set-up before the recorder) is the set-up table's
    own = [e for e in events if e["ts"] >= 0] or events
    t0 = min(e["ts"] for e in own)
    t1 = max(e["ts"] + e.get("dur", 0.0) for e in own)
    wall = t1 - t0

    tracks = _attributed_tracks(events)
    phases = {}
    spans = []
    monotone_ok = True
    for track, track_events in sorted(tracks.items()):
        track_events.sort(key=lambda e: e["ts"])
        prev_end = None
        durs = [e.get("dur", 0.0) for e in track_events]
        for e in track_events:
            if prev_end is not None and e["ts"] < prev_end - OVERLAP_TOL_S:
                monotone_ok = False
            prev_end = e["ts"] + e.get("dur", 0.0)
            spans.append((e["ts"], prev_end))
        phases[track[len(MAIN_TRACK_PREFIX):]] = {
            "seconds": round(sum(durs), 6),
            "count": len(durs),
            "mean_ms": round(1e3 * sum(durs) / len(durs), 3),
            "max_ms": round(1e3 * max(durs), 3),
        }
    # the cross-track invariant that makes the table sum to wall: all
    # attributed spans live on the main thread, so they must be globally
    # non-overlapping, not just per track
    spans.sort()
    for (s0, e0), (s1, _) in zip(spans, spans[1:]):
        if s1 < e0 - OVERLAP_TOL_S:
            monotone_ok = False

    attributed = sum(p["seconds"] for p in phases.values())
    steady = wall - attributed
    boundaries = [
        e["args"] for e in tracks.get(MAIN_TRACK_PREFIX + "flush", ())
        if "dispatch_s" in e.get("args", {})
    ]
    dispatch = sum(a["dispatch_s"] for a in boundaries)
    for name, p in phases.items():
        p["share"] = round(p["seconds"] / wall, 4) if wall > 0 else 0.0

    anomalies = []
    for name, p in phases.items():
        bar = ANOMALY_SHARES.get(name)
        if bar is not None and p["share"] > bar:
            anomalies.append({
                "phase": name,
                "flag": f"share {p['share']:.0%} > {bar:.0%}",
            })
    event_counts = {}
    for e in events:
        if e.get("ph") == "i" and e["name"] in EVENT_FLAGS:
            event_counts[e["name"]] = event_counts.get(e["name"], 0) + 1
    for name, count in sorted(event_counts.items()):
        anomalies.append({
            "phase": "events", "flag": f"{EVENT_FLAGS[name]} (x{count})",
        })

    nonnegative_ok = steady >= -OVERLAP_TOL_S
    return {
        **encoder_section(events),
        "phases": phases,
        "steady_state": {
            "seconds": round(steady, 6),
            "share": round(steady / wall, 4) if wall > 0 else 0.0,
            # inside the update calls (counters, not spans) / the rest
            "dispatch_s": round(dispatch, 6),
            "rest_s": round(steady - dispatch, 6),
            # the longest single update call: one blocked call (a recompile,
            # a stalled device) hides in a window's sum, not in its max
            "dispatch_max_ms": round(1e3 * max(
                (a["dispatch_max_s"] for a in boundaries), default=0.0), 3),
        },
        "anomalies": anomalies,
        "consistency": {
            "wall_s": round(wall, 6),
            "attributed_s": round(attributed, 6),
            "steady_state_s": round(steady, 6),
            "monotone_ok": monotone_ok,
            "nonnegative_ok": bool(nonnegative_ok),
            # the gate bit: the table sums to the measured wall time (exact
            # by construction) AND that construction was valid — attributed
            # spans non-overlapping and the remainder non-negative
            "ok": bool(monotone_ok and nonnegative_ok and wall > 0),
        },
        "n_events": len(events),
    }


def encoder_section(events):
    """``{"encoder": ...}`` for a run whose encoder has expert layers: what
    ``train.supcon.plan_experts`` said at build (the ``expert_plan`` event on
    track ``compile``) and the newest ``health_window`` means of the
    encoder's own ring columns, which the event names (``ring_columns``),
    with ``plan_sparse_attention``'s ``sparse_attention_plan`` event,
    ``plan_latent_attention``'s ``latent_attention_plan`` event or
    ``plan_linear_attention``'s ``linear_attention_plan`` event; nothing for
    a ResNet's run."""
    plan = next((e["args"] for e in events if e["name"] == "expert_plan"), None)
    if plan is None:
        return {}
    last = {}
    for e in events:
        if e["name"] == "health_window":
            last.update({k: e["args"][k] for k in plan.get("ring_columns", ())
                         if k in e.get("args", {})})
    section = {"expert_plan": plan, "ring": last}
    section.update({"attention_plan": e["args"] for e in events
                    if e["name"] == "sparse_attention_plan"})
    section.update({name: e["args"] for e in events for name in (
        "latent_attention_plan", "linear_attention_plan") if e["name"] == name})
    return {"encoder": section}


# ------------------------------------------------------------------ set-up


def _union(spans):
    """Sorted disjoint intervals covering the (start, end) pairs."""
    out = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def _minus(spans, cover):
    """The parts of the disjoint intervals ``spans`` outside ``cover``."""
    out = []
    for a, b in spans:
        for c, d in cover:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def _seconds(spans):
    return sum(b - a for a, b in spans)


def build_setup(events, top=10):
    """The set-up table (pure): from the process's start (the earliest
    record where none says it) to the first ``flush_boundary``, split into
    rows that never share a second, so they sum to that wall. In order of
    precedence: ``compile`` (union of ``backend_compile`` spans: XLA's
    compile or the persistent cache's read), ``trace_lower`` (union of
    ``trace`` and ``lower`` spans, less compile), then ``boot`` (process
    start to the package's first line) and each ``setup`` span by name
    (``import``, ``backend_start``, ``store``, ``tb_writer``, ...), then
    what is left of ``first_step`` and the rest of the first flush window
    (``first_window``), each less what a row above holds; ``other`` is what
    no record covers. Unions, never sums: a function traced inside another
    has a ``trace`` span inside the outer one. Also the ``top`` programs
    with the most compile seconds, with their cache hits. None without a
    ``flush_boundary``."""
    ends = [e["ts"] for e in events
            if e["name"] == "flush_boundary" and e.get("ph") == "X"]
    if not ends:
        return None
    t1 = min(ends)
    marks = {e["name"]: e["ts"] for e in events if e.get("track") == tracing.SETUP_TRACK
             and e.get("ph") != "X"}
    t0 = marks.get(tracing.PROCESS_START, min(e["ts"] for e in events))

    def spans(keep):
        return _union([(max(e["ts"], t0), min(e["ts"] + e["dur"], t1)) for e in events
                       if e.get("ph") == "X" and keep(e)
                       and e["ts"] < t1 and e["ts"] + e["dur"] > t0])

    compiles = [e for e in events if e["name"] == "backend_compile" and e.get("ph") == "X"
                and t0 <= e["ts"] < t1]
    rows, covered = {}, []

    def row(name, intervals):
        own = _minus(_union(intervals), covered)
        rows[name] = rows.get(name, 0.0) + _seconds(own)
        covered[:] = _union(covered + own)

    row("compile", spans(lambda e: e["name"] == "backend_compile"))
    row("trace_lower", spans(lambda e: e.get("track") == tracing.COMPILE_TRACK
                             and e["name"] in ("trace", "lower")))
    if tracing.PROCESS_START in marks and tracing.PACKAGE_IMPORT in marks:
        row("boot", [(t0, marks[tracing.PACKAGE_IMPORT])])
    for name in dict.fromkeys(e["name"] for e in events
                              if e.get("track") == tracing.SETUP_TRACK and e.get("ph") == "X"):
        row(name, spans(lambda e: e.get("track") == tracing.SETUP_TRACK and e["name"] == name))
    first = [e for e in events if e["name"] == "first_step" and e.get("ph") == "X"
             and e["ts"] < t1]
    if first:
        row("first_step", spans(lambda e: e is first[0]))
        row("first_window", [(first[0]["ts"] + first[0]["dur"], t1)])
    wall = t1 - t0
    rows["other"] = wall - sum(rows.values())
    programs = {}
    for e in compiles:
        p = programs.setdefault(e["args"].get("fun_name", "?"), [0.0, 0, 0])
        p[0] += e["dur"]
        p[1] += 1
        p[2] += bool(e["args"].get("cache_hit"))
    dearest = sorted(programs.items(), key=lambda kv: -kv[1][0])[:top]
    return {
        "wall_s": round(wall, 6),
        "process_start": tracing.PROCESS_START in marks,
        "rows": {k: round(v, 6) for k, v in rows.items()},
        "programs": [{"fun_name": name, "seconds": round(sec, 6), "compiles": n,
                      "cache_hits": hits} for name, (sec, n, hits) in dearest],
    }


def render_setup(setup):
    wall = setup["wall_s"]
    head = "process start" if setup["process_start"] else "first record"
    lines = [f"set-up, {head} to the first flush boundary: {wall:.3f}s"]
    for name, sec in setup["rows"].items():
        share = sec / wall if wall > 0 else 0.0
        lines.append(f"  {name:<14}{sec:>10.3f}s {share:>7.1%}")
    if setup["programs"]:
        lines.append("dearest programs (compile or cache read):")
    for p in setup["programs"]:
        misses = p["compiles"] - p["cache_hits"]
        lines.append(f"  {p['seconds']:>10.3f}s  {p['fun_name']}  "
                     f"({p['cache_hits']} hit, {misses} miss)")
    return "\n".join(lines)


def render_table(report):
    rows = [("phase", "seconds", "share", "count", "mean_ms", "max_ms")]
    for name, p in sorted(
        report["phases"].items(), key=lambda kv: -kv[1]["seconds"]
    ):
        rows.append((
            name, f"{p['seconds']:.3f}", f"{p['share']:.1%}",
            str(p["count"]), f"{p['mean_ms']:.1f}", f"{p['max_ms']:.1f}",
        ))
    ss = report["steady_state"]
    rows.append((
        "steady_state", f"{ss['seconds']:.3f}", f"{ss['share']:.1%}",
        "-", "-", "-",
    ))
    wall = report["consistency"]["wall_s"]
    for name, key, max_ms in (
        ("  dispatch", "dispatch_s", f"{ss['dispatch_max_ms']:.1f}"),
        ("  rest", "rest_s", "-"),
    ):
        share = ss[key] / wall if wall > 0 else 0.0
        rows.append((name, f"{ss[key]:.3f}", f"{share:.1%}", "-", "-", max_ms))
    rows.append((
        "wall", f"{report['consistency']['wall_s']:.3f}", "100.0%",
        "-", "-", "-",
    ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    lines = [
        "  ".join(cell.ljust(w) for cell, w in zip(row, widths))
        for row in rows
    ]
    lines.insert(1, "-" * len(lines[0]))
    if "encoder" in report:
        plan, ring = report["encoder"]["expert_plan"], report["encoder"]["ring"]
        lines.append(
            f"experts: {plan['layers']} layers hold {plan['held']} of "
            f"{plan['n_experts']}, {plan['per_token']} a token"
            + (f" ({plan['router']}-routed, after {plan['dense_layers']} dense layers, "
               f"shared experts of width {plan['shared_width']}"
               + (" under a sigmoid gate" if plan.get("shared_gate") else "") + ")"
               if "router" in plan else "")
            + f", {plan['rows_per_step']} token rows a step, "
            f"{plan.get('provisioned_assignments', 0)} assignments a layer swept whatever "
            f"the routing in {plan.get('provisioned_trips', '?')} trips of "
            f"{plan.get('rows_per_trip', '?')} rows"
            + (f", grouped products on {plan['product_operands']} operands"
               + (f" ({plan['product_reason']})" if plan.get("product_reason") else "")
               if "product_operands" in plan else "")
            + "; " + (", ".join(
                f"{k} {v:.4g}" for k, v in ring.items()) or "no health window yet"))
        attention = report["encoder"].get("attention_plan")
        if attention:
            lines.append(
                f"sparse attention: {attention['engaged']} layers on the kernel pair, "
                f"{attention['on_xla']} on XLA's path" + "".join(
                    f"; {', '.join(names)}: {why}"
                    for why, names in attention["reasons"].items()))
        latent = report["encoder"].get("latent_attention_plan")
        if latent:
            lines.append(
                f"latent attention: {latent['layers']} layers of {latent['heads']} heads "
                f"({latent['nope_dim']} + {latent['rope_dim']} shared rotary / "
                f"{latent['v_dim']}), latent of {latent['kv_rank']}, {latent['tokens']} "
                f"tokens a row, on {latent['path']}'s path: {latent['reason']}")
        linear = report["encoder"].get("linear_attention_plan")
        if linear:
            kinds, reasons, conv_reasons = linear["layers"], {}, {}
            for layer in linear["per_layer"]:
                if layer["reason"]:
                    reasons.setdefault(layer["reason"], []).append(layer["name"])
                if layer.get("conv_reason"):
                    conv_reasons.setdefault(layer["conv_reason"], []).append(layer["name"])
            lines.append(
                f"linear attention: {kinds.get('linear', 0)} Gated DeltaNet layers of "
                f"{linear['key_heads']} key / {linear['value_heads']} value heads of "
                f"{linear['key_dim']} / {linear['value_dim']} beside "
                f"{sum(kinds.values()) - kinds.get('linear', 0)} full, "
                f"{linear['conv_width']}-tap convolution, scan in chunks of {linear['chunk']} "
                f"of {linear['tokens']} tokens, {linear['row_group']} rows a group, "
                f"{linear['engaged']} on the kernel pair, {linear['on_xla']} on XLA's path"
                + "".join(f"; {', '.join(names)}: {why}" for why, names in reasons.items())
                + (f"; convolution: {linear['conv_engaged']} on its kernel pair, "
                   f"{linear['conv_on_xla']} on XLA's path" + "".join(
                       f"; {', '.join(names)}: {why}" for why, names in conv_reasons.items())
                   if "conv_engaged" in linear else ""))
    for a in report["anomalies"]:
        lines.append(f"ANOMALY [{a['phase']}]: {a['flag']}")
    if not report["consistency"]["ok"]:
        lines.append("CONSISTENCY: FAILED (overlapping or oversubscribed "
                     "attribution — recorder track contract violated)")
    return "\n".join(lines)


def build_output(events_path, report):
    """The committed artifact (pure; schema pinned by tests)."""
    return {"schema": SCHEMA, "events": events_path, "report": report}


# ------------------------------------------------------------------ fleet


def anchor_points(events):
    """``{anchor_seq: local_ts}`` of one process's clock anchors."""
    out = {}
    for e in events:
        if e.get("name") == ANCHOR_EVENT and e.get("ph") == "i":
            args = e.get("args", {})
            if "anchor" in args:
                out[int(args["anchor"])] = float(e["ts"])
    return out


def fit_alignment(ref_anchors, anchors):
    """Affine map local -> reference clock over the matched anchor seqs
    (pure). Least squares over >=2 anchors recovers offset AND rate drift;
    one anchor degrades to offset-only (scale pinned at 1); zero matched
    anchors means the timelines cannot be merged (``residual_s`` None).
    ``residual_s`` is the MAX absolute fit error — the merge's error bar,
    gated against :data:`FLEET_RESIDUAL_TOL_S`."""
    seqs = sorted(set(ref_anchors) & set(anchors))
    n = len(seqs)
    if n == 0:
        return {"scale": 1.0, "offset_s": 0.0, "residual_s": None,
                "n_anchors": 0}
    xs = [anchors[s] for s in seqs]
    ys = [ref_anchors[s] for s in seqs]
    if n == 1:
        a, b = 1.0, ys[0] - xs[0]
    else:
        mx, my = sum(xs) / n, sum(ys) / n
        sxx = sum((x - mx) ** 2 for x in xs)
        sxy = sum((x - mx) * (y - my) for x, y in zip(xs, ys))
        a = sxy / sxx if sxx > 0 else 1.0
        b = my - a * mx
    residual = max(abs(a * x + b - y) for x, y in zip(xs, ys))
    return {"scale": a, "offset_s": round(b, 6),
            "residual_s": round(residual, 6), "n_anchors": n}


def _aligned(alignment, ts):
    return alignment["scale"] * ts + alignment["offset_s"]


def build_fleet_report(events_by_process, residual_tol_s=FLEET_RESIDUAL_TOL_S):
    """One session's merged fleet view (pure — tests drive it on synthetic
    per-process event lists).

    ``events_by_process`` maps process index -> that process's records.
    The lowest process index is the reference clock; every other process
    is affine-fitted onto it through the matched ``clock_anchor`` events.
    Collective spans (``main:collective``) are matched across processes by
    (name, per-process occurrence index) — valid because the collective
    call SCHEDULE is identical across processes (the repo's documented
    deadlock invariant); a span's start is that process's ARRIVAL at the
    boundary, so the aligned arrival spread is the boundary's skew and the
    latest arrival is its straggler.
    """
    if not events_by_process:
        raise ValueError("no per-process event lists: empty fleet?")
    pids = sorted(events_by_process)
    ref = pids[0]
    anchors = {p: anchor_points(events_by_process[p]) for p in pids}
    alignments = {
        p: fit_alignment(anchors[ref], anchors[p]) for p in pids
    }

    processes = {}
    attribution_ok = True
    for p in pids:
        try:
            rep = build_report(events_by_process[p])
            ok = bool(rep["consistency"]["ok"])
        except ValueError:
            ok = False
        attribution_ok = attribution_ok and ok
        processes[str(p)] = {
            "n_events": len(events_by_process[p]),
            "n_anchors": len(anchors[p]),
            "alignment": alignments[p],
            "attribution_ok": ok,
        }

    # collective spans, grouped by (name, occurrence) across processes;
    # skew is a CROSS-process spread, so a single-process merge has no
    # skew table (not a table of zeros)
    groups = {}
    for p in (pids if len(pids) > 1 else ()):
        counters = {}
        for e in events_by_process[p]:
            if e.get("ph") == "X" and e.get("track") == COLLECTIVE_TRACK:
                i = counters.get(e["name"], 0)
                counters[e["name"]] = i + 1
                groups.setdefault((e["name"], i), {})[p] = {
                    "arrival": _aligned(alignments[p], e["ts"]),
                    "wait_s": e.get("dur", 0.0),
                    "step": e.get("args", {}).get("step"),
                }
    skew_table = []
    incomplete = 0
    times_last = {p: 0 for p in pids}
    lateness = {p: [] for p in pids}
    for (name, i), by_p in groups.items():
        if set(by_p) != set(pids):
            # a process died (or went silent) before this boundary: real
            # finding on a preempted run, merge-contract violation on a
            # clean one — counted either way, skewless
            incomplete += 1
            continue
        arrivals = {p: by_p[p]["arrival"] for p in pids}
        first = min(arrivals.values())
        straggler = max(pids, key=lambda p: arrivals[p])
        for p in pids:
            lateness[p].append(arrivals[p] - first)
        times_last[straggler] += 1
        skew_table.append({
            "name": name, "index": i, "step": by_p[ref]["step"],
            "t_s": round(first, 6),
            "skew_s": round(arrivals[straggler] - first, 6),
            "straggler": straggler,
            "arrivals_s": {str(p): round(arrivals[p], 6) for p in pids},
        })
    skew_table.sort(key=lambda r: r["t_s"])
    ranking = sorted(
        (
            {
                "process": p,
                "times_last": times_last[p],
                "boundaries": len(lateness[p]),
                "mean_lateness_s": round(
                    sum(lateness[p]) / len(lateness[p]), 6
                ) if lateness[p] else 0.0,
            }
            for p in pids
        ),
        key=lambda r: (-r["times_last"], -r["mean_lateness_s"]),
    )

    non_ref = pids[1:]
    residuals = [alignments[p]["residual_s"] for p in non_ref]
    aligned_ok = all(
        alignments[p]["n_anchors"] >= 2
        and alignments[p]["residual_s"] is not None
        and alignments[p]["residual_s"] <= residual_tol_s
        for p in non_ref
    )
    collective_match_ok = incomplete == 0
    consistency = {
        "n_processes": len(pids),
        "aligned_ok": bool(aligned_ok),
        "max_residual_s": max([r for r in residuals if r is not None],
                              default=0.0),
        "residual_tol_s": residual_tol_s,
        "attribution_ok": bool(attribution_ok),
        "collective_match_ok": bool(collective_match_ok),
        "incomplete_boundaries": incomplete,
        # the gate bit: timelines really merged (every non-ref process
        # anchored to sub-tolerance), every per-process attribution holds,
        # every collective boundary is whole, and a multi-process merge
        # produced at least one skew observation (none = the fleet
        # instrumentation was silently dead)
        "ok": bool(
            aligned_ok and attribution_ok and collective_match_ok
            and (len(pids) == 1 or len(skew_table) > 0)
        ),
    }
    return {
        "processes": processes,
        "skew_table": skew_table,
        "straggler_ranking": ranking,
        "consistency": consistency,
    }


def fleet_chrome_trace(events_by_process, report):
    """The merged Chrome trace: every process's records mapped onto the
    reference clock (its fitted alignment), ``pid`` = process index, the
    whole fleet shifted so the earliest record sits at t=0 (Chrome/Perfetto
    dislike negative timestamps)."""
    aligned = {}
    t0 = None
    for p, events in sorted(events_by_process.items()):
        al = report["processes"][str(p)]["alignment"]
        evs = []
        for e in events:
            e2 = dict(e, ts=_aligned(al, e["ts"]))
            if "dur" in e2:
                e2["dur"] = e2["dur"] * al["scale"]
            evs.append(e2)
            t0 = e2["ts"] if t0 is None else min(t0, e2["ts"])
        aligned[p] = evs
    out = {"traceEvents": [], "displayTimeUnit": "ms"}
    for p, evs in sorted(aligned.items()):
        trace = chrome_trace_from_events(
            [dict(e, ts=e["ts"] - t0) for e in evs], process_index=p
        )
        out["traceEvents"].extend(trace["traceEvents"])
    return out


def render_fleet_table(report, max_rows=12):
    lines = []
    rows = [("process", "events", "anchors", "scale", "offset_s",
             "residual_s", "attribution")]
    for p, info in sorted(report["processes"].items(), key=lambda kv: int(kv[0])):
        al = info["alignment"]
        res = al["residual_s"]
        rows.append((
            p, str(info["n_events"]), str(info["n_anchors"]),
            f"{al['scale']:.9g}", f"{al['offset_s']:.6f}",
            "-" if res is None else f"{res:.6f}",
            "ok" if info["attribution_ok"] else "FAILED",
        ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    lines += ["  ".join(c.ljust(w) for c, w in zip(r, widths)) for r in rows]
    lines.insert(1, "-" * len(lines[0]))
    table = sorted(report["skew_table"], key=lambda r: -r["skew_s"])[:max_rows]
    if table:
        lines.append(f"boundary skew (top {len(table)} by skew):")
        for r in table:
            lines.append(
                f"  {r['name']}[{r['index']}] step={r['step']} "
                f"t={r['t_s']:.3f}s skew={r['skew_s'] * 1e3:.1f}ms "
                f"straggler=p{r['straggler']}"
            )
    for r in report["straggler_ranking"]:
        if r["boundaries"]:
            lines.append(
                f"straggler ranking: p{r['process']} last at "
                f"{r['times_last']}/{r['boundaries']} boundaries "
                f"(mean lateness {r['mean_lateness_s'] * 1e3:.1f}ms)"
            )
    cons = report["consistency"]
    if not cons["ok"]:
        lines.append(f"CONSISTENCY: FAILED ({cons})")
    return "\n".join(lines)


def build_fleet_output(run_dir, session_reports):
    """The committed fleet artifact (pure; schema pinned by tests):
    one report per recorder session, ``ok`` = every session merged
    consistently."""
    return {
        "schema": FLEET_SCHEMA,
        "run_dir": run_dir,
        "sessions": session_reports,
        "ok": bool(session_reports) and all(
            rep["consistency"]["ok"] for rep in session_reports.values()
        ),
    }


def run_fleet(args):
    sessions = tracing.discover_fleet_sessions(args.fleet)
    if not sessions:
        print(f"no events*.jsonl sessions in {args.fleet}")
        return 1
    reports = {}
    last = None
    for label, files in sessions.items():
        # EVERY discovered process file enters the merge, records or not: a
        # process whose file exists but holds zero complete records (a
        # SIGKILL before its first full line) is exactly the dead-process
        # post-mortem this mode exists to surface — silently dropping it
        # would let a 2-process session merge "consistently" as one
        events_by_process = {
            pidx: load_events(path) for pidx, path in sorted(files.items())
        }
        report = build_fleet_report(events_by_process)
        report["files"] = {
            str(p): os.path.basename(files[p]) for p in events_by_process
        }
        reports[label] = report
        last = (events_by_process, report)
        print(f"== session {label} "
              f"({report['consistency']['n_processes']} process(es)) ==")
        print(render_fleet_table(report))
    artifact = build_fleet_output(args.fleet, reports)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {args.json}")
    if args.trace and last is not None:
        # the merged Chrome trace of the LATEST session (the one a
        # post-mortem usually wants — earlier sessions stay per-process)
        with open(args.trace, "w") as f:
            json.dump(fleet_chrome_trace(*last), f)
        print(f"wrote {args.trace}")
    return 0 if artifact["ok"] else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", default="",
                    help="a flight-recorder events.jsonl (run dir artifact)")
    ap.add_argument("--fleet", default="", metavar="RUN_DIR",
                    help="fleet mode: merge every per-process "
                         "events*_p*.jsonl session in this run dir "
                         "(clock-anchor alignment, skew table, straggler "
                         "ranking)")
    ap.add_argument("--json", default="",
                    help="write the attribution/fleet artifact here")
    ap.add_argument("--trace", default="",
                    help="(fleet) write the merged Chrome trace here")
    args = ap.parse_args(argv)
    if bool(args.events) == bool(args.fleet):
        ap.error("exactly one of --events / --fleet is required")

    if args.fleet:
        return run_fleet(args)
    events = load_events(args.events)
    report = build_report(events)
    print(render_table(report))
    setup = build_setup(events)
    if setup is not None:
        print(render_setup(setup))
    if args.json:
        out = build_output(args.events, report)
        if setup is not None:
            out["setup"] = setup
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}")
    return 0 if report["consistency"]["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
