"""From a profiler trace to numbers: the one reduction every PR is read by.

A trace is held as plain data,

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns, {stat: value}], ...]}]}]}

which ``load_xplane`` makes from the profiler's ``.xplane.pb`` with nothing but
JAX, and which ``tests/`` writes by hand. Everything below works on that form.
Device planes are those named ``/device:TPU:<n>``; the operations of a chip's
core are on its ``XLA Ops`` line, whole executions of a compiled program on
its ``XLA Modules`` line.

``python benchmark/trace_reduce.py --peek <trace dir or .xplane.pb>`` prints
planes, lines and the heaviest events with their stats: look before you
write a rule.
"""

from __future__ import annotations

import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast|send|recv)(-start|-done)?([.\d]*)$")


def find_xplane(path: str) -> str:
    if os.path.isdir(path):
        hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not hits:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        return hits[-1]
    return path


def load_xplane(path: str, host_events=(), lines=(OPS_LINE, MODULES_LINE),
                with_stats: bool = False) -> dict:
    """Of the device planes the named ``lines`` (all of them when None); of
    the host's planes only the events whose name is in ``host_events`` (the
    harness's clock anchor). Stats are read only for ``--peek``: no reduction
    uses them, and reading them is most of the time a large trace takes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        if not on_device and not host_events:
            continue
        kept = []
        for line in plane.lines:
            if on_device and lines is not None and line.name not in lines:
                continue
            events = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns),
                 {k: str(v) for k, v in ev.stats} if with_stats and on_device else {}]
                for ev in line.events if on_device or ev.name in host_events]
            if events:
                kept.append({"name": line.name, "events": events})
        if kept:
            planes.append({"name": plane.name, "lines": kept})
    return {"planes": planes}


def device_planes(trace: dict) -> list:
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return sorted(planes, key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def events_of(plane: dict, line_name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return sorted(line["events"], key=lambda e: e[1])
    return []


def find_host_event(trace: dict, name: str):
    """Start (ns) of the first host event of that name, or None."""
    starts = [e[1] for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
              for ln in p["lines"] for e in ln["events"] if e[0] == name]
    return min(starts) if starts else None


# ------------------------------------------------------------- intervals


def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, t0, t1) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """The parts of merged intervals ``a`` that no interval of ``b`` covers."""
    b = merge(b)
    out = []
    for s, e in merge(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def spans(events) -> list:
    return [(e[1], e[1] + e[2]) for e in events]


# --------------------------------------------------------- classification
#
# This runtime's trace gives an operation nothing but its name, which is the
# text of its HLO instruction, and no category. What an operation is comes
# from the compiled program's own text (``hlo_kinds``), looked up by the
# instruction's name; the name's own opcode decides where no text is given.


def instruction(event) -> str:
    """'%fusion.12 = f32[..] fusion(..)' -> 'fusion.12'."""
    return event[0].split(" = ", 1)[0].strip().lstrip("%")


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def hlo_kinds(hlo_text: str) -> dict:
    """instruction name -> 'conv', 'pallas' or 'collective', for the
    instructions of a compiled module's text that are a convolution or a
    fusion whose body holds one, a Mosaic (Pallas) custom call, or a
    collective. Every other instruction is left out."""
    has_conv, body_of, kinds, calls = {}, None, {}, {}
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            body_of = m.group(1)
            has_conv.setdefault(body_of, False)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        if opcode == "convolution":
            kinds[name] = "conv"
            if body_of is not None:
                has_conv[body_of] = True
        elif opcode == "custom-call" and "tpu_custom_call" in line:
            kinds[name] = "pallas"
        elif COLLECTIVE.match(opcode):
            kinds[name] = "collective"
        elif opcode == "fusion":
            c = _CALLS.search(line)
            if c:
                calls[name] = c.group(1)
    for name, body in calls.items():
        if has_conv.get(body):
            kinds[name] = "conv"
    return kinds


def kind_of(event, kinds: dict = None) -> str:
    name = instruction(event)
    if kinds and name in kinds:
        return kinds[name]
    if COLLECTIVE.match(name):
        return "collective"
    if name.startswith("convolution"):
        return "conv"
    return ""


def is_kind(kind: str, kinds: dict = None):
    return lambda event: kind_of(event, kinds) == kind


# ------------------------------------------------------------- reduction


def step_intervals(plane: dict, program: str) -> list:
    """Whole executions of the step program on this chip, in order."""
    return [(e[1], e[1] + e[2]) for e in events_of(plane, MODULES_LINE) if program in e[0]]


def steady_stretch(plane: dict, program: str):
    """(t0, t1, steps): from the start of the first whole step in the trace
    to the start of the last one, so that the idle time after each counted
    step is inside the stretch. The first execution seen is left out: the
    trace may have begun inside it. None when too few executions are there.
    """
    steps = step_intervals(plane, program)[1:]
    if len(steps) < 3:
        return None
    return steps[0][0], steps[-1][0], len(steps) - 1


def busy_seconds(plane: dict, t0: float, t1: float) -> float:
    return total(merge(clip(spans(events_of(plane, OPS_LINE)), t0, t1))) / 1e9


def idle_gaps(plane: dict, t0: float, t1: float) -> list:
    """(start_ns, seconds) of every stretch inside [t0, t1] with no operation
    on the core."""
    return [(s, (e - s) / 1e9) for s, e in subtract([(t0, t1)], clip(spans(events_of(plane, OPS_LINE)), t0, t1))]


def seconds_where(plane: dict, t0: float, t1: float, pred) -> float:
    evs = [e for e in events_of(plane, OPS_LINE) if pred(e)]
    return total(merge(clip(spans(evs), t0, t1))) / 1e9


def per_step_max(planes, stretches, pred) -> float:
    """Seconds per step of the operations ``pred`` picks, on the chip where
    that is largest."""
    return max(seconds_where(p, t0, t1, pred) / steps
               for p, (t0, t1, steps) in zip(planes, stretches))


def exposed_collective_seconds(plane: dict, t0: float, t1: float, kinds: dict = None) -> float:
    """Time inside collective operations during which no other operation
    runs on that chip."""
    ops = events_of(plane, OPS_LINE)
    collective = is_kind("collective", kinds)
    coll = clip(spans([e for e in ops if collective(e)]), t0, t1)
    rest = clip(spans([e for e in ops if not collective(e)]), t0, t1)
    return total(subtract(coll, rest)) / 1e9


def top_ops(plane: dict, t0: float, t1: float, kinds: dict = None, n: int = 10) -> list:
    """[[name, seconds]] of the operations that took most time over the
    stretch, summed by the instruction's name as the trace gives it, with
    its kind beside it where one is known."""
    sums = {}
    for e in events_of(plane, OPS_LINE):
        s, end = max(e[1], t0), min(e[1] + e[2], t1)
        if end <= s:
            continue
        kind = kind_of(e, kinds)
        key = f"{instruction(e)}[{kind}]" if kind else instruction(e)
        sums[key] = sums.get(key, 0.0) + (end - s) / 1e9
    return [[k, v] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def seconds_by_kind(plane: dict, t0: float, t1: float, kinds: dict = None) -> dict:
    """Seconds of the stretch under each kind ('' for the rest)."""
    sums = {}
    for e in events_of(plane, OPS_LINE):
        s, end = max(e[1], t0), min(e[1] + e[2], t1)
        if end > s:
            k = kind_of(e, kinds) or "other"
            sums[k] = sums.get(k, 0.0) + (end - s) / 1e9
    return sums


def peek(path: str, n: int = 40) -> None:
    trace = load_xplane(path, lines=None, with_stats=True)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(find_xplane(path)).planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(f"plane {plane.name!r}: {lines[:12]}{' ...' if len(lines) > 12 else ''}")
    for plane in device_planes(trace)[:1]:
        for line in plane["lines"]:
            evs = line["events"]
            print(f"== {plane['name']} / {line['name']}: {len(evs)} events")
            sums = {}
            for e in evs:
                k = instruction(e)
                agg = sums.setdefault(k, [0.0, 0, e])
                agg[0] += e[2]
                agg[1] += 1
            for k, (dur, cnt, ex) in sorted(sums.items(), key=lambda kv: -kv[1][0])[:n]:
                stats = {a: (str(b)[:100]) for a, b in ex[3].items()}
                print(f"  {dur / 1e6:10.3f} ms  x{cnt:<6d} {k[:60]:60s} {stats}")


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--peek":
        peek(sys.argv[2])
    else:
        sys.exit(__doc__)
