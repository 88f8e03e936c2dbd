"""Flight recorder: unified span/event tracing + the stall watchdog.

Every subsystem this repo grew — the zero-sync metric ring, the windowed
device store, the pipelined serve executor, the collective
preemption/placement decisions — observes itself in its own private way,
and the failure class the code works hardest to prevent (a split collective
decision deadlocking the pod) is exactly the one that produces NO
diagnostic output at all. This module is the shared answer:

- :class:`FlightRecorder` — a thread-safe span/event recorder with an
  injectable monotonic clock, appending one JSON line per record to a
  per-run ``events.jsonl`` and exporting a Chrome-trace/Perfetto-loadable
  ``trace.json`` on close. Only HOST-VISIBLE boundaries are instrumented
  (flush boundaries, window swaps, checkpoint submit/commit, collective
  decisions, epoch edges, serve request stages), so the dispatch-only hot
  loop gains zero device syncs or transfers — asserted mechanically in
  tier-1 through the existing injectable ``device_get``/``index_put``
  hooks (tests/test_tracing.py).

- :class:`StallWatchdog` — a background thread that fires when the
  observed progress beat (the drivers' flush boundary; the serve
  completer) hasn't advanced within a deadline, dumping ALL thread stacks
  via ``faulthandler`` plus a recorder snapshot into the run dir. A silent
  collective deadlock becomes an attributable artifact instead of an
  opaque hang that burns the preemption grace window.

Track convention (what ``scripts/trace_report.py`` attributes): spans on
``main:*`` tracks are main-thread phases that never nest ACROSS tracks —
they partition the epoch loop's wall clock, so the report's attribution
table (compile / data / flush / checkpoint / collective / ... /
steady-state) sums to the measured wall time. ``main:epoch`` is the one
exception: an envelope track the report uses for context, excluded from
attribution. Tracks owned by other threads (``telemetry:*``,
``prefetch:*``, ``serve:*``) carry no such invariant (concurrent serve
requests overlap by design).

Set-up is on the record too, on one clock from the process's start
(``time.monotonic``): each recorder on that clock opens with a
``process_start`` event at a negative ``ts`` (read once from
``/proc/self/stat``; Linux only, elsewhere nothing is recorded), the track
``setup`` holds spans that never overlap each other (``import``, ``store``,
``backend_start``, ``tb_writer``), and the track ``compile`` JAX's own
``trace``, ``lower`` and ``backend_compile`` spans per program
(:func:`forward_compile_events`). ``setup`` is not a ``main:*`` track, so
a main-thread phase may nest inside a set-up span.

The module-level ``install``/``span``/``event`` helpers follow the
``logging`` pattern: instrumentation sites call ``tracing.span(...)``
unconditionally and pay only a global read + a no-op context manager when
no recorder is installed — deep modules (telemetry, device_store,
checkpoint, preempt, the serve batcher) need no recorder threading through
their signatures. The one exception is the time before the process's first
``install``: records on ``setup`` and ``compile`` then wait in a small
module buffer, and the first install hands them to its recorder.
"""

from __future__ import annotations

import contextlib
import faulthandler
import functools
import json
import logging
import os
import re
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, Iterable, List, Optional, Tuple

logger = logging.getLogger(__name__)

# main-thread phase tracks: the non-nesting attribution convention above
MAIN_TRACK_PREFIX = "main:"
# the envelope track excluded from attribution (it CONTAINS the others)
EPOCH_TRACK = "main:epoch"
# the fleet track: clock anchors + per-boundary skew observations — the
# records scripts/trace_report.py --fleet aligns multi-process timelines on
FLEET_TRACK = "fleet"
ANCHOR_EVENT = "clock_anchor"
# set-up, from the process's start to the first step (module docstring)
SETUP_TRACK = "setup"
COMPILE_TRACK = "compile"
PROCESS_START = "process_start"
PACKAGE_IMPORT = "package_import"
# the clock of records made before any recorder exists: a recorder on
# another clock can place neither them nor the process's start
_setup_clock = time.monotonic


class FlightRecorder:
    """Thread-safe span/event recorder behind one lock.

    Records live in a bounded in-memory ring (``snapshot`` — what the
    watchdog dumps) and, when ``path`` is given, are appended to an
    ``events.jsonl`` file as they land. ``clock`` must be monotonic;
    timestamps are stored relative to construction time, so records from
    different processes align only per-file (one recorder per process,
    ``recorder_for_run``). On the set-up clock (``time.monotonic``, the
    default) the first record is ``process_start`` on track ``setup``, at a
    negative ``ts``.
    """

    def __init__(
        self,
        path: Optional[str] = None,
        clock: Callable[[], float] = time.monotonic,
        max_events: int = 65536,
        trace_path: Optional[str] = None,
        process_index: int = 0,
    ):
        self._clock = clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self._ring: "deque[dict]" = deque(maxlen=max_events)
        self._path = path
        self._trace_path = trace_path
        self._file = None
        self._closed = False
        self.process_index = int(process_index)
        self.dropped = 0  # records lost to the ring bound (jsonl keeps all)
        self._anchor_seq = 0  # clock_anchor sequence (see clock_anchor)
        if clock is _setup_clock:
            started = process_start()
            if started is not None:
                self.record_event(PROCESS_START, SETUP_TRACK, started)

    # ------------------------------------------------------------ record
    def _emit(self, rec: dict) -> None:
        line = json.dumps(rec, default=str)
        with self._lock:
            if self._closed:
                return
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(rec)
            if self._path is not None:
                if self._file is None:
                    self._file = open(self._path, "a")
                self._file.write(line + "\n")
                # flush every record: a flight recorder exists for the runs
                # that DON'T exit cleanly (SIGKILL after the grace window, a
                # wedged collective) — a userspace buffer would lose exactly
                # the last, most interesting records. Records land only at
                # host boundaries (a few per window / per request), so the
                # per-record flush is noise there; no fsync — surviving a
                # kernel crash is not the contract.
                self._file.flush()

    def now(self) -> float:
        """The recorder's clock (absolute; records store ``now() - t0``)."""
        return self._clock()

    def event(self, name: str, track: str = "events", **attrs) -> None:
        """An instantaneous event (Chrome ``ph: "i"``)."""
        self.record_event(name, track, self._clock(), **attrs)

    def record_event(self, name: str, track: str, at: float, /, **attrs) -> None:
        """An instantaneous event at an explicit value of this recorder's
        clock (see :meth:`record_span`); positional, so that an attribute
        may be called ``at``."""
        rec = {
            "name": name, "track": track, "ph": "i",
            "ts": round(at - self._t0, 6),
        }
        if attrs:
            rec["args"] = attrs
        self._emit(rec)

    def clock_anchor(self, kind: str, **attrs) -> int:
        """Record a fleet clock anchor and return its sequence number.

        Anchors are stamped at ALREADY-MATCHED collective points (the
        startup placement agreement, each flush-boundary failure-code
        allgather) right AFTER the collective releases — on a pod every
        process leaves the allgather at (approximately) the same real
        instant, so anchor ``seq`` k is the same physical moment observed
        through each process's unaligned monotonic clock. That makes the
        per-process ``(seq, ts)`` pairs an alignment ruler:
        ``scripts/trace_report.py --fleet`` fits one affine map per process
        over them and merges the timelines. The sequence is deterministic
        because the collective call SCHEDULE is (the documented invariant
        of those call sites — a mismatched count is already a deadlock).
        Single-process runs record the same events (host-only, zero device
        cost); they simply carry no cross-process information.
        """
        with self._lock:
            self._anchor_seq += 1
            seq = self._anchor_seq
        self.event(ANCHOR_EVENT, track=FLEET_TRACK, kind=kind, anchor=seq,
                   **attrs)
        return seq

    def record_span(
        self, name: str, track: str, start: float, end: float, **attrs
    ) -> None:
        """A completed span from explicit clock values.

        ``start``/``end`` must come from THIS recorder's clock domain
        (``now()`` or the same injected clock) — the cross-thread spelling
        the serve batcher uses to stamp a request at submit and record it
        at completion on another thread.
        """
        rec = {
            "name": name, "track": track, "ph": "X",
            "ts": round(start - self._t0, 6),
            "dur": round(max(0.0, end - start), 6),
        }
        if attrs:
            rec["args"] = attrs
        self._emit(rec)

    @contextlib.contextmanager
    def span(self, name: str, track: str, **attrs):
        start = self._clock()
        try:
            yield
        finally:
            self.record_span(name, track, start, self._clock(), **attrs)

    # ------------------------------------------------------------ output
    def snapshot(self, last: Optional[int] = None) -> List[dict]:
        """The most recent records (all, or the last ``last``) — what the
        watchdog attaches to a stall dump."""
        with self._lock:
            records = list(self._ring)
        return records if last is None else records[-last:]

    def flush(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.flush()

    def export_chrome_trace(self, path: Optional[str] = None) -> dict:
        """The Chrome-trace/Perfetto view of the in-memory ring; written to
        ``path`` (or the constructor's ``trace_path``) when given."""
        trace = chrome_trace_from_events(
            self.snapshot(), process_index=self.process_index
        )
        path = path or self._trace_path
        if path:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(trace, f)
            os.replace(tmp, path)
        return trace

    def close(self) -> None:
        """Flush the jsonl, export ``trace.json`` (when configured), and
        stop accepting records. Never raises — it runs in driver
        ``finally`` blocks where a raise would mask the real failure."""
        with self._lock:
            if self._closed:
                return
        if self.dropped:
            # a saturated ring means trace.json and watchdog snapshots are
            # truncated (the jsonl keeps everything): leave the count on
            # the durable record so trace_report can flag it as a finding
            self.event(
                "recorder_dropped", track="events", records=self.dropped
            )
        try:
            self.export_chrome_trace()
        except OSError as e:  # disk full on the way out: keep the exit clean
            logger.warning("flight recorder: trace export failed (%s)", e)
        with self._lock:
            self._closed = True
            if self._file is not None:
                try:
                    self._file.flush()
                    self._file.close()
                except OSError:
                    pass
                self._file = None


def chrome_trace_from_events(events: Iterable[dict], process_index: int = 0) -> dict:
    """Chrome trace-event JSON from recorder records (pure; schema pinned by
    tests/test_tracing.py). Tracks map to integer ``tid``s with
    ``thread_name`` metadata; ``ts``/``dur`` are integer microseconds, moved
    so that none is negative (set-up records precede the recorder)."""
    events = list(events)
    base = min([0.0] + [rec["ts"] for rec in events])
    tids: dict = {}
    out = []
    for rec in events:
        track = rec.get("track", "events")
        tid = tids.get(track)
        if tid is None:
            tid = tids[track] = len(tids) + 1
        ev = {
            "name": rec["name"],
            "ph": "X" if rec.get("ph") == "X" else "i",
            "pid": process_index,
            "tid": tid,
            "ts": int(round((rec["ts"] - base) * 1e6)),
            "args": rec.get("args", {}),
        }
        if ev["ph"] == "X":
            ev["dur"] = int(round(rec.get("dur", 0.0) * 1e6))
        else:
            ev["s"] = "t"  # instant-event scope: thread
        out.append(ev)
    meta = [
        {
            "name": "thread_name", "ph": "M", "pid": process_index, "tid": tid,
            "args": {"name": track},
        }
        for track, tid in tids.items()
    ]
    return {"traceEvents": meta + out, "displayTimeUnit": "ms"}


EVENTS_FILE = "events.jsonl"
TRACE_FILE = "trace.json"

# events[_pN][_rK].jsonl — process N (absent = 0), session K (absent = 1)
EVENTS_FILE_RE = re.compile(r"^(events(?:_p(\d+))?)(?:_r(\d+))?\.jsonl$")


def parse_jsonl(text: str) -> Tuple[List[dict], int]:
    """Parse recorder jsonl text into ``(records, consumed)``.

    The ONE torn-line-tolerant reader behind ``load_events_jsonl``,
    ``scripts/trace_report.py``, ``scripts/health_report.py``, and the
    supervisor's ``RunDirWatcher``: only COMPLETE lines (through the last
    newline) are consumed — the half-written final line a SIGKILL (or a
    reader racing the writer) leaves behind is exactly the run the
    recorder exists to diagnose, so it must never crash the reader.
    Complete-but-corrupt lines are skipped, not raised. ``consumed`` is
    the offset just past the last newline — the incremental-tail
    bookkeeping the watcher keeps per file.
    """
    consumed = text.rfind("\n") + 1
    records: List[dict] = []
    for line in text[:consumed].splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict):
            records.append(rec)
    return records, consumed


def load_events_jsonl(path: str) -> List[dict]:
    """All complete records of one recorder jsonl (torn-line tolerant)."""
    with open(path) as f:
        return parse_jsonl(f.read())[0]


def session_files_for(events_path: str) -> List[str]:
    """Every session file of the PROCESS ``events_path`` belongs to, in
    session order: ``events.jsonl``, ``events_r2.jsonl``, ... (or the
    ``events_pN*`` family). A resumed run rotates to a fresh ``_rK`` file
    per session (:func:`run_paths`), so a reader that stops at the first
    file silently truncates the timeline at the first preemption. Unknown
    file names return just themselves."""
    d, fname = os.path.split(events_path)
    m = EVENTS_FILE_RE.match(fname)
    if not m:
        return [events_path]
    base = m.group(1)
    out = []
    k = 1
    while True:
        name = f"{base}.jsonl" if k == 1 else f"{base}_r{k}.jsonl"
        path = os.path.join(d, name)
        if not os.path.exists(path):
            break
        out.append(path)
        k += 1
    return out or [events_path]


def discover_fleet_sessions(run_dir: str) -> Dict[str, Dict[int, str]]:
    """All recorder sessions in a run dir, grouped for the fleet view:
    ``{"r1": {0: ".../events.jsonl", 1: ".../events_p1.jsonl"}, "r2": ...}``
    — one entry per session, mapping process index -> that process's
    events file. Sessions align only within themselves (timestamps restart
    per session), so the fleet report merges each session independently."""
    sessions: Dict[int, Dict[int, str]] = {}
    for fname in sorted(os.listdir(run_dir)):
        m = EVENTS_FILE_RE.match(fname)
        if not m:
            continue
        pidx = int(m.group(2) or 0)
        k = int(m.group(3) or 1)
        sessions.setdefault(k, {})[pidx] = os.path.join(run_dir, fname)
    return {f"r{k}": files for k, files in sorted(sessions.items())}


def run_paths(run_dir: str, process_index: int = 0):
    """Per-process, per-SESSION recorder file names inside one (shared)
    run dir.

    Timestamps are relative to each recorder's construction, so a resumed
    run (the exit-75 relaunch loop lands in the SAME save_folder) must not
    append a second ts~0 timeline into the first session's file — that
    would read as overlapping main-thread spans and fail trace_report's
    attribution on exactly the preempted runs the recorder exists to
    diagnose. Each session therefore gets the first unused ``_rK`` suffix:
    ``events.jsonl``, ``events_r2.jsonl``, ... (and the matching
    ``trace*.json``), one self-consistent timeline per file.
    """
    base = "events" if process_index == 0 else f"events_p{process_index}"
    tbase = "trace" if process_index == 0 else f"trace_p{process_index}"
    session = ""
    k = 1
    while os.path.exists(os.path.join(run_dir, f"{base}{session}.jsonl")):
        k += 1
        session = f"_r{k}"
    return (
        os.path.join(run_dir, f"{base}{session}.jsonl"),
        os.path.join(run_dir, f"{tbase}{session}.json"),
    )


def recorder_for_run(
    run_dir: str, enabled: bool = True, clock: Callable[[], float] = time.monotonic
) -> Optional[FlightRecorder]:
    """The drivers' one-call recorder factory: ``events.jsonl`` +
    ``trace.json`` in the run dir (per-process suffixes on a pod — every
    host keeps its own story; a pod post-mortem reads all of them — and
    per-session suffixes across resumes, see :func:`run_paths`)."""
    if not enabled or not run_dir:
        return None
    import jax  # lazy: this module must stay importable without jax

    pidx = jax.process_index()
    os.makedirs(run_dir, exist_ok=True)
    events, trace = run_paths(run_dir, pidx)
    return FlightRecorder(
        events, clock=clock, trace_path=trace, process_index=pidx
    )


# ---------------------------------------------------------------- current
# logging-style module-level recorder: instrumentation sites stay one-line
# and cost a global read when no recorder is installed.

_current: Optional[FlightRecorder] = None
# Records on these tracks made before the process's first install wait here,
# stamped by the set-up clock, as (name, track, start, end or None, attrs);
# None from the first install on (of a recorder or of None).
_EARLY_TRACKS = frozenset((SETUP_TRACK, COMPILE_TRACK))
EARLY_RECORDS_MAX = 256
_early: Optional[list] = []
_early_lock = threading.Lock()


def install(recorder: Optional[FlightRecorder]) -> None:
    """Make ``recorder`` the module's (None: none). The process's first
    install hands the records made before it to ``recorder`` where it runs
    on the set-up clock, and ends the buffering either way."""
    global _current, _early
    with _early_lock:
        early, _early = _early, None
    if early and recorder is not None and recorder._clock is _setup_clock:
        for name, track, start, end, attrs in early:
            if end is None:
                recorder.record_event(name, track, start, **attrs)
            else:
                recorder.record_span(name, track, start, end, **attrs)
    _current = recorder


def uninstall() -> None:
    install(None)


def current() -> Optional[FlightRecorder]:
    return _current


def _keep_early(name: str, track: str, start: float, end: Optional[float],
                attrs: dict) -> None:
    with _early_lock:
        if _early is not None and len(_early) < EARLY_RECORDS_MAX:
            _early.append((name, track, start, end, attrs))


def _buffering(track: str) -> bool:
    return _early is not None and track in _EARLY_TRACKS


@contextlib.contextmanager
def span(name: str, track: str, **attrs):
    rec = _current
    if rec is not None:
        with rec.span(name, track, **attrs):
            yield
    elif _buffering(track):
        start = _setup_clock()
        try:
            yield
        finally:
            _keep_early(name, track, start, _setup_clock(), attrs)
    else:
        yield


def event(name: str, track: str = "events", **attrs) -> None:
    rec = _current
    if rec is not None:
        rec.event(name, track, **attrs)
    elif _buffering(track):
        _keep_early(name, track, _setup_clock(), None, attrs)


def clock_anchor(kind: str, **attrs) -> Optional[int]:
    """Record a fleet clock anchor on the installed recorder (no-op
    ``None`` without one) — see :meth:`FlightRecorder.clock_anchor`."""
    rec = _current
    if rec is None:
        return None
    return rec.clock_anchor(kind, **attrs)


def record_span(name: str, track: str, start: float, end: float, **attrs) -> None:
    rec = _current
    if rec is not None:
        rec.record_span(name, track, start, end, **attrs)
    elif _buffering(track):
        _keep_early(name, track, start, end, attrs)


def record_event(name: str, track: str, at: float, /, **attrs) -> None:
    rec = _current
    if rec is not None:
        rec.record_event(name, track, at, **attrs)
    elif _buffering(track):
        _keep_early(name, track, at, None, attrs)


# ---------------------------------------------------------------- set-up
# Where set-up starts: the process (``process_start``, from the kernel) and
# the package (``package_import``, the package's own first line).

_imports_recorded = False


@functools.lru_cache(maxsize=1)
def _started_after_boot() -> Optional[float]:
    """Seconds after boot at which this process started: field 22 of
    ``/proc/<pid>/stat`` (clock ticks since boot) over ``SC_CLK_TCK``; None
    where the file or the clock is missing (not Linux)."""
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
        # field 2, the command, is in parentheses and may hold spaces
        ticks = int(stat[stat.rindex(")") + 2:].split()[22 - 3])
        return ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def process_start() -> Optional[float]:
    """The process's start on the set-up clock, or None where the system
    does not say (then nothing is recorded: it is never estimated).
    ``/proc`` is read once; the age is taken on ``CLOCK_BOOTTIME``, the
    clock the kernel's start time counts on, to the tick (10 ms)."""
    started = _started_after_boot()
    boottime = getattr(time, "CLOCK_BOOTTIME", None)
    if started is None or boottime is None:
        return None
    return _setup_clock() - (time.clock_gettime(boottime) - started)


def imports_done() -> None:
    """Record the set-up span ``import``: from the package's first line
    (``simclr_pytorch_distributed_tpu.IMPORT_STARTED``, where a
    ``package_import`` event marks it) to now. Once a process, at the first
    of two places: the program's first ask of the backend
    (``parallel/mesh.py``, which flag parsing reaches, and which opens the
    span ``backend_start`` next), and the entry of
    ``train.supcon.enable_compile_cache``, where the drivers' imports and
    flag parsing end."""
    global _imports_recorded
    if _imports_recorded:
        return
    _imports_recorded = True
    package = sys.modules.get(__name__.split(".")[0])
    started = getattr(package, "IMPORT_STARTED", None)
    if started is None:
        return
    record_event(PACKAGE_IMPORT, SETUP_TRACK, started)
    record_span("import", SETUP_TRACK, started, _setup_clock())


# ---------------------------------------------------------------- compiles
# The run's own compile record on track ``compile``: JAX's three phases of
# every program as spans, ``trace`` (Python to jaxpr), ``lower`` (jaxpr to
# the compiler's module) and ``backend_compile`` (XLA's compile, or its read
# from the persistent cache: ``cache_hit`` says which; ``duration_s`` its
# seconds), each with the jitted function's ``fun_name``. A function traced
# inside another has a ``trace`` span of its own inside the outer one, so a
# reader takes unions of these spans, never sums. An operator whose run
# recompiles (resume on another mesh, a second layout of the update) sees
# which program, which phase and for how long, not one slow step.

_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_compile_listeners_on = False
# JAX fires the cache-hit event on the compiling thread inside the backend
# compile whose span it then closes
_cache_hit = threading.local()


def forward_compile_events() -> None:
    """Register (once a process; jax.monitoring keeps listeners for good)
    the listeners that forward the compile phases to whichever recorder is
    installed when a program compiles, or before the first install to the
    module's buffer. Afterwards with no recorder, no work."""
    global _compile_listeners_on
    if _compile_listeners_on:
        return
    import jax  # lazy: this module must stay importable without jax

    # JAX stamps the spans with time.time(): one offset, taken here, puts
    # them on the set-up clock
    wall_ahead = time.time() - _setup_clock()

    def on_span(event, start, end, **kw):
        name = _COMPILE_SPANS.get(event)
        if name is None:
            return
        if name == "backend_compile":
            hit = getattr(_cache_hit, "hit", False)
            _cache_hit.hit = False
            # fun_name: this JAX version passes it; an older one passes none
            kw.update(duration_s=round(end - start, 6), cache_hit=hit)
        record_span(name, COMPILE_TRACK, start - wall_ahead, end - wall_ahead, **kw)

    def on_event(event, **kw):
        if event == _CACHE_HIT:
            _cache_hit.hit = True

    jax.monitoring.register_event_time_span_listener(on_span)
    jax.monitoring.register_event_listener(on_event)
    _compile_listeners_on = True


# ---------------------------------------------------------------- watchdog


class StallWatchdog:
    """Fires when the progress beat hasn't advanced within ``deadline_s``.

    The drivers beat at every ``print_freq`` flush boundary (wired through
    ``TelemetrySession``), the serve batcher beats as in-flight batches
    complete — exactly the points whose silence means a stalled collective,
    a wedged device, or a deadlocked pipeline. On fire it writes two
    artifacts into ``dump_dir``:

    - ``stall_dump_N.txt`` — every thread's Python stack
      (``faulthandler.dump_traceback``), i.e. WHERE each host thread is
      blocked (the collective call, the queue wait, the D2H);
    - ``stall_dump_N.json`` — the stall metadata plus a
      :class:`FlightRecorder` snapshot (what the run was doing on the way
      in), when a recorder is attached.

    One dump per stall: after firing it stays quiet until a beat re-arms
    it. ``check()`` is the testable core — the fake-clock tier-1 tests
    drive it directly (``start=False``), the background thread merely calls
    it on a real-time cadence. The watchdog only OBSERVES (no recovery
    action): killing or resuming a wedged collective from a watchdog thread
    would trade a diagnosable hang for corrupted state.
    """

    def __init__(
        self,
        deadline_s: float,
        dump_dir: str,
        clock: Callable[[], float] = time.monotonic,
        recorder: Optional[FlightRecorder] = None,
        poll_s: Optional[float] = None,
        start: bool = True,
        name: str = "train",
        armed: bool = True,
    ):
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be positive, got {deadline_s}")
        self.deadline_s = float(deadline_s)
        self.dump_dir = dump_dir
        self.name = name
        self._clock = clock
        self._recorder = recorder
        self._lock = threading.Lock()
        self._last = clock()
        self._armed = bool(armed)
        self._fired = False
        self.dumps = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        if start:
            # real-time polling cadence; staleness itself is judged against
            # the injectable clock, so tests never depend on this thread
            self._poll_s = poll_s if poll_s is not None else max(
                1.0, self.deadline_s / 4.0
            )
            self._thread = threading.Thread(
                target=self._run, name=f"stall-watchdog-{name}", daemon=True
            )
            self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self._poll_s):
            self.check()

    # ------------------------------------------------------------- beats
    def beat(self) -> None:
        """Progress observed: reset the deadline and re-arm the next dump."""
        with self._lock:
            self._last = self._clock()
            self._fired = False

    def arm(self) -> None:
        """Start watching (beats first — arming is itself progress)."""
        with self._lock:
            self._last = self._clock()
            self._fired = False
            self._armed = True

    def disarm(self) -> None:
        """Stop watching (e.g. the serve pipeline went idle: silence is
        expected, not a stall)."""
        with self._lock:
            self._armed = False

    # ------------------------------------------------------------- check
    def check(self) -> bool:
        """Evaluate the deadline now; returns True iff a dump was written
        by THIS call."""
        with self._lock:
            if not self._armed or self._fired:
                return False
            age = self._clock() - self._last
            if age <= self.deadline_s:
                return False
            self._fired = True
            self.dumps += 1
            n = self.dumps
        self._dump(age, n)
        return True

    def _dump(self, age: float, n: int) -> None:
        txt_path = os.path.join(self.dump_dir, f"stall_dump_{n}.txt")
        try:
            os.makedirs(self.dump_dir, exist_ok=True)
            with open(txt_path, "w") as f:
                f.write(
                    f"STALL: {self.name} progress beat stalled for "
                    f"{age:.1f}s (deadline {self.deadline_s:.1f}s); "
                    f"all thread stacks follow\n"
                )
                f.flush()
                faulthandler.dump_traceback(file=f, all_threads=True)
        except OSError as e:  # the watchdog must never kill the run
            logger.error("stall watchdog: stack dump failed (%s)", e)
        if self._recorder is not None:
            self._recorder.event(
                "stall_detected", track="watchdog", age_s=round(age, 3),
                deadline_s=self.deadline_s, dump=n,
            )
            self._recorder.flush()
            json_path = os.path.join(self.dump_dir, f"stall_dump_{n}.json")
            try:
                with open(json_path, "w") as f:
                    json.dump(
                        {
                            "name": self.name,
                            "age_s": round(age, 3),
                            "deadline_s": self.deadline_s,
                            "dump": n,
                            "events": self._recorder.snapshot(last=512),
                        },
                        f, default=str,
                    )
            except OSError as e:
                logger.error("stall watchdog: snapshot dump failed (%s)", e)
        logger.error(
            "STALL: %s progress beat stalled for %.1fs (deadline %.1fs); "
            "thread stacks dumped to %s", self.name, age, self.deadline_s,
            txt_path,
        )

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
