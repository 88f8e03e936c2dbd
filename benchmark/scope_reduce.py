"""Device time by scope: which layer of the step each operation belongs to.

The trace names an operation by its HLO instruction and nothing else
(``trace_reduce``). The compiled step's text says where each instruction came
from: ``metadata={op_name="jit(ring_update)/jvp(SupConResNet)/encoder/
layer2_block1/bn1/mul"}``: flax's module path, the step's named scopes
(``train/supcon_step.py``: data, aug, loss, optimizer, ring), and
``transpose(jvp(...))`` on everything of the backward pass. ``scope_map``
reads that text once; ``seconds_by_scope`` gives every busy instant of a
stretch to exactly one bucket, so the buckets and ``unattributed`` sum to the
stretch's busy seconds.

The text comes from the program itself, on demand
(``utils.profiling.step_program_text``), for the benchmark's readers
(``scope_seconds(run)``) and for an operator alike:

    python benchmark/scope_reduce.py <trace dir>

prints the table for a ``--trace_dir`` capture that has
``step_program.hlo.txt`` beside it (``StepTracer`` writes it), and how much
of the traced time ran under instructions that text does not hold.
"""

from __future__ import annotations

import os
import re
import sys

import trace_reduce as tr

STAGES = ("layer1", "layer2", "layer3", "layer4")
BUCKETS = ("data", "aug", "stem") + STAGES + ("head", "loss", "optimizer", "ring")
ENCODER = ("stem",) + STAGES + ("head",)
UNATTRIBUTED = "unattributed"
STEP_PROGRAM_FILE = "step_program.hlo.txt"  # as utils/profiling.StepTracer writes it

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WORD = re.compile(r"[A-Za-z_][\w.]*")
_STAGE = re.compile(r"^(layer[1-4])_block\d+$")
_MODULE = re.compile(r"^HloModule jit_(\w+)", re.M)
# the step's own named scopes (train/supcon_step.STEP_SCOPES): a bucket each
_STEP_SCOPES = tuple(b for b in BUCKETS if b not in ENCODER)


def _scope_names(op_name: str) -> list:
    """The scope of each path component under the program's own ``jit(..)``:
    ``transpose(jvp(loss))`` is the scope ``loss`` under two transformations;
    ``jit(norm)`` is a jitted function's own name, not a scope."""
    names = []
    for part in op_name.split("/")[1:]:
        words = _WORD.findall(part)
        names.append("" if not words or "jit" in words[:-1] else words[-1])
    return names


def bucket_of(op_name: str):
    """(bucket, direction) of one ``op_name``, or None when the path names no
    bucket. The innermost scope decides (a recipe's second forward inside the
    loss is the encoder's time); ``encoder/conv1``, ``encoder/bn1`` and what
    sits directly under ``encoder`` (the stem's ReLU, the 4x4 average pool)
    are the stem."""
    if not op_name.startswith("jit("):
        return None  # a parameter: its op_name is the argument's own path
    names = _scope_names(op_name)
    bucket = None
    for i, name in enumerate(names):
        if name in _STEP_SCOPES:
            bucket = name
        elif name == "proj_head":
            bucket = "head"
        elif name == "encoder":
            stage = _STAGE.match(names[i + 1]) if i + 1 < len(names) else None
            bucket = stage.group(1) if stage else "stem"
    if bucket is None:
        return None
    return bucket, "bwd" if "transpose(" in op_name else "fwd"


def scope_map(hlo_text: str) -> dict:
    """instruction name -> (bucket, direction), for every instruction of a
    compiled module's text that names a bucket: by its own ``op_name`` (a
    fusion's is its root's), and for a fusion without one by the majority of
    its body. Instructions that name none are left out."""
    own, votes, calls, body_of = {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = tr._COMPUTATION.match(line)
        if m:
            body_of = m.group(1)
            continue
        m = tr._INSTRUCTION.match(line)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        meta = _OP_NAME.search(line)
        where = bucket_of(meta.group(1)) if meta else None
        if where is not None:
            own[name] = where
            if body_of is not None:
                tally = votes.setdefault(body_of, {})
                tally[where] = tally.get(where, 0) + 1
        elif opcode == "fusion":
            c = tr._CALLS.search(line)
            if c:
                calls[name] = c.group(1)
    for name, body in calls.items():
        tally = votes.get(body)
        if tally:
            own[name] = max(sorted(tally), key=tally.get)
    return own


def seconds_by_scope(plane: dict, t0: float, t1: float, scopes: dict) -> dict:
    """{(bucket, direction): seconds} over [t0, t1] on one chip, with
    ``(UNATTRIBUTED, "")`` for operations the map does not hold. Every busy
    instant goes to one operation, the one that started last (an operation
    inside a ``while`` owns its time, the ``while`` what is left), so the
    values sum to ``trace_reduce.busy_seconds``."""
    # by start, the longer first: of two that start together the inner is last
    events = sorted(
        ((max(e[1], t0), min(e[1] + e[2], t1), tr.instruction(e))
         for e in tr.events_of(plane, tr.OPS_LINE) if e[1] + e[2] > t0 and e[1] < t1),
        key=lambda ev: (ev[0], -ev[1]))
    owned, active, cursor = {}, [], t0
    for start, end, name in events + [(t1, t1, None)]:
        while cursor < start:
            while active and active[-1][0] <= cursor:
                active.pop()
            if not active:
                cursor = start
                break
            until = min(start, active[-1][0])
            owned[active[-1][1]] = owned.get(active[-1][1], 0.0) + until - cursor
            cursor = until
        if name is not None and end > start:
            active.append((end, name))
    sums = {}
    for name, ns in owned.items():
        key = scopes.get(name, (UNATTRIBUTED, ""))
        sums[key] = sums.get(key, 0.0) + ns / 1e9
    return sums


def bucket_seconds(by_scope: dict, buckets, direction: str = None) -> float:
    return sum(v for (b, d), v in by_scope.items()
               if b in buckets and direction in (None, d))


def program_text():
    """(name, text) of the step the program registered, or None: nothing was
    registered, no call was noted, or the program predates the registry."""
    try:
        from simclr_pytorch_distributed_tpu.utils import profiling
    except ImportError:
        return None
    reader = getattr(profiling, "step_program_text", None)
    return reader() if reader is not None else None


def scope_seconds(run: dict):
    """What the per-scope readers share, computed once a run and kept in
    ``run``: {"by_scope", "busy_s", "steps"} over the steady stretch of the
    chip that idles most, or None where there is no trace or no text."""
    if "scope_seconds" not in run:
        run["scope_seconds"] = None
        program = program_text() if run.get("stretches") else None
        if program is not None:
            plane = run["planes"][run["worst"]]
            t0, t1, steps = run["stretches"][run["worst"]]
            run["scope_seconds"] = {
                "by_scope": seconds_by_scope(plane, t0, t1, scope_map(program[1])),
                "busy_s": tr.busy_seconds(plane, t0, t1), "steps": steps}
    return run["scope_seconds"]


def ms_per_step(run: dict, buckets):
    """Milliseconds a step in ``buckets``, forward and backward, or None."""
    got = scope_seconds(run)
    if got is None:
        return None
    return 1e3 * bucket_seconds(got["by_scope"], buckets) / got["steps"]


def window_records(run: dict) -> list:
    """The flight recorder's records inside the measured window; none where
    the window's marks are missing."""
    marks = {r["name"]: r["ts"] for r in run["records"] if r.get("track") == "bench"}
    if "bench_window_start" not in marks or "bench_window_end" not in marks:
        return []
    t0, t1 = marks["bench_window_start"], marks["bench_window_end"]
    return [r for r in run["records"] if t0 <= r["ts"] <= t1]


def table(by_scope: dict, busy_s: float, steps: int) -> str:
    rows = [f"{'bucket':<14}{'fwd ms/step':>13}{'bwd ms/step':>13}{'share':>9}"]
    for bucket in BUCKETS + (UNATTRIBUTED,):
        both = bucket_seconds(by_scope, (bucket,))
        bwd = bucket_seconds(by_scope, (bucket,), "bwd")
        rows.append(f"{bucket:<14}{1e3 * (both - bwd) / steps:>13.3f}{1e3 * bwd / steps:>13.3f}"
                    f"{both / busy_s:>9.1%}")
    rows.append(f"{'busy':<14}{1e3 * busy_s / steps:>13.3f}{'':>13}{1:>9.1%}")
    return "\n".join(rows)


def main(argv) -> int:
    if len(argv) != 1 or argv[0].startswith("-"):
        sys.exit(__doc__)
    trace_dir = argv[0]
    with open(os.path.join(trace_dir, STEP_PROGRAM_FILE)) as f:
        text = f.read()
    header = _MODULE.search(text)
    if header is None:
        sys.exit(f"{STEP_PROGRAM_FILE}: no 'HloModule jit_<program>' line")
    program, scopes = header.group(1), scope_map(text)
    held = {m.group(1): ("held", "") for m in map(tr._INSTRUCTION.match, text.splitlines()) if m}
    planes = tr.device_planes(tr.load_xplane(trace_dir))
    if not planes:
        print(f"{trace_dir}: no TPU plane in the trace")
    for plane in planes:
        stretch = tr.steady_stretch(plane, program)
        if stretch is None:
            print(f"{plane['name']}: no steady stretch of {program}")
            continue
        t0, t1, steps = stretch
        busy_s = tr.busy_seconds(plane, t0, t1)
        print(f"{plane['name']}: {steps} steps of {program}, {(t1 - t0) / 1e6 / steps:.3f} ms a step")
        print(table(seconds_by_scope(plane, t0, t1, scopes), busy_s, steps))
        # another program's operations, or a text that is not the program the
        # traced steps ran (then the rows above are not to be trusted)
        missing = seconds_by_scope(plane, t0, t1, held).get((UNATTRIBUTED, ""), 0.0)
        print(f"under instructions the text does not hold: {missing / busy_s:.2%} of the busy time")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
