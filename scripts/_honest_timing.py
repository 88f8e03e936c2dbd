"""Shared honest-sync timing harness for the scripts/ benchmarks.

Seconds per iteration of ``core``, dispatch amortized: ``iters`` iterations
run INSIDE one jitted ``fori_loop`` (each chained on the previous scalar, so
the loop cannot be parallelized or hoisted), one dispatch + one
computed-scalar readback per window. A separate 1-iteration program measures
the dispatch+readback floor, subtracted from the per-iter quotient. On the
round-5 machine the floor was ~2 ms — larger than the kernels being measured —
which is why a python-loop-of-dispatches cannot resolve these shapes (see
docs/PERF.md "Measurement methodology").

``core(i, lead, *rest)`` receives the loop index ``i`` (for per-iteration
randomness via ``fold_in``; ignore it for fixed inputs) and ``lead`` =
``args[0]`` perturbed by the carried scalar — the data-dependence that chains
each iteration on the previous one. It must return a scalar that depends on
the iteration's computation (so nothing is dead-code-eliminated).
"""

import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np


def time_per_iter(core, args, iters=100, windows=5):
    """Median seconds per iteration of ``core`` over ``windows`` windows."""
    if iters < 2:
        # the dispatch floor is subtracted via the (iters - 1) quotient below:
        # iters=1 would divide by zero AFTER the warmup compiles, and iters<1
        # would silently mismeasure — fail loudly before any work instead
        # (callers pass CLI --iters values straight through)
        raise ValueError(f"iters must be >= 2 to subtract the dispatch floor, got {iters}")

    def make(n_iters):
        @jax.jit
        def run(tick, *a):
            def body(i, t):
                lead = a[0] + t * 1e-20  # data-dependence on the prior iter
                return t + core(i, lead, *a[1:])
            return jax.lax.fori_loop(0, n_iters, body, tick)
        return run

    looped, single = make(iters), make(1)
    tick = jnp.float32(0.0)
    float(looped(tick, *args))  # compile+warm
    float(single(tick, *args))

    def window_times(fn):
        dts = []
        for _ in range(windows):
            t = jnp.float32(0.0)
            t0 = time.perf_counter()
            out = float(fn(t, *args))  # computed-scalar readback: the only real sync
            dts.append(time.perf_counter() - t0)
            assert np.isfinite(out)
        return statistics.median(dts)

    floor = window_times(single)           # dispatch + readback + 1 iter
    total = window_times(looped)           # dispatch + readback + N iters
    return max(total - floor, 0.0) / (iters - 1)
