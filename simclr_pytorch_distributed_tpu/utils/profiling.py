"""Windowed jax.profiler trace capture (SURVEY.md §5: the reference has only
wall-clock AverageMeters, no profiler at all — this is the TPU-native upgrade).

A ``StepTracer`` starts a TensorBoard-loadable trace at ``start_step`` and
stops it ``num_steps`` later, skipping the compile-dominated first iterations.
View with ``tensorboard --logdir <trace_dir>`` (Profile tab) or xprof.

The trace names a device operation by its HLO instruction and nothing else;
which layer an instruction belongs to is in the compiled program's text (each
instruction's ``op_name`` carries the flax module path and the step's named
scopes, train/supcon_step.py). :func:`step_program_text` is the one way to
that text, for the operator (``StepTracer`` writes it beside the trace as
``step_program.hlo.txt``) and for the benchmark's readers alike. Nothing is
lowered or compiled unless someone asks: the driver only registers its jitted
program and notes, on its compile step, the abstract signature of the calls
that follow.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional, Tuple

import jax

from simclr_pytorch_distributed_tpu.utils import tracing

STEP_PROGRAM_FILE = "step_program.hlo.txt"
# one instant on both clocks, the profiler's and the flight recorder's, so a
# --trace_dir capture can be laid against events.jsonl
TRACE_ANCHOR = "trace_anchor"

# the driver loop's program: (name, jitted function) and the abstract
# arguments of its steady-state calls. Process-wide like the installed
# flight recorder: the readers have no handle on the driver's locals (the
# benchmark wraps the update in its own callable). train.supcon.run clears
# it on its way out.
_step_program: Optional[tuple] = None
_step_signature = None


def register_step_program(name: str, jitted) -> None:
    """Called where the driver loop's update is jitted
    (``train.supcon.make_fused_update``); the latest registration wins and
    forgets the signature noted for an earlier program."""
    global _step_program, _step_signature
    _step_program, _step_signature = (name, jitted), None


def note_step_signature(args) -> None:
    """Shape, dtype and sharding of every leaf of ``args``: one ``tree.map``,
    on the driver's compile step only, of the arguments the NEXT call gets
    (the state the compiling call returned is committed to the program's
    shardings; the fresh one it was given is not, and a program compiled
    for that one is not the one the traced steps run). Arrays are not
    kept."""
    global _step_signature

    def abstract(x):
        # an uncommitted array (the base key, a host batch) may sit
        # anywhere: the program's own in_shardings place it
        sharding = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    _step_signature = jax.tree.map(abstract, args)


def clear_step_program() -> None:
    """Forget the program and its signature (a run's end; tests)."""
    global _step_program, _step_signature
    _step_program = _step_signature = None


def step_program_text() -> Optional[Tuple[str, str]]:
    """``(program name, compiled text)`` of the registered step program under
    the noted signature, or None when either is missing. Lowers and compiles
    on every call: a fraction of a second in the process that ran the step
    (the jit's own trace and executable answer; 0.2-0.4 s on the v5e,
    PERF.md), a trace of the step plus a compile or cache read anywhere
    else, so only readers of a profile call it."""
    if _step_program is None or _step_signature is None:
        return None
    name, jitted = _step_program
    return name, jitted.lower(*_step_signature).compile().as_text()


class StepTracer:
    def __init__(
        self,
        trace_dir: str,
        start_step: int = 10,
        num_steps: int = 10,
        enabled: bool = True,
    ):
        self.trace_dir = trace_dir
        self.start_step = start_step
        self.stop_step = start_step + num_steps
        self.enabled = bool(trace_dir) and enabled
        self._active = False

    def step(self, global_step: int) -> None:
        """Call once per training step with the global step index."""
        if not self.enabled:
            return
        # >= not ==: after a checkpoint resume the first observed step may
        # already be past start_step; still capture a window.
        if not self._active and global_step >= self.start_step:
            jax.profiler.start_trace(self.trace_dir)
            self._active = True
            with jax.profiler.TraceAnnotation(TRACE_ANCHOR):
                tracing.event(TRACE_ANCHOR, track="profile", step=global_step)
            logging.info("profiler: tracing steps [%d, %d) -> %s",
                         self.start_step, self.stop_step, self.trace_dir)
            self.stop_step = global_step + self.stop_step - self.start_step
        elif self._active and global_step >= self.stop_step:
            self.close()

    def close(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self.enabled = False  # one window per run
            self._write_step_program()

    def _write_step_program(self) -> None:
        """The compiled step's text beside the trace, AFTER the capture: the
        lowering holds the main thread for seconds, which inside the traced
        window would drain the device's queue and be read as idle time.
        Never raises (close() runs in the drivers' ``finally``)."""
        try:
            t0 = time.perf_counter()
            program = step_program_text()
            if program is None:
                return
            path = os.path.join(self.trace_dir, STEP_PROGRAM_FILE)
            with open(path, "w") as f:
                f.write(program[1])
            logging.info("profiler: %s's compiled text -> %s (%.2f s)",
                         program[0], path, time.perf_counter() - t0)
        except Exception:  # noqa: BLE001 — a profile aid must not fail the run
            logging.exception("profiler: could not write %s", STEP_PROGRAM_FILE)
