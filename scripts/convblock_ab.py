#!/usr/bin/env python
"""Do the fused Pallas conv-block kernels delete the inter-op HBM
round-trips that fund XLA's stage-1 conv/BN/residual fusions — for every
admitted block kind and compute dtype?

Two claims per block kind, one committed artifact
(docs/evidence/convblock_ab_r19.json, schema convblock_ab/v2):

**Parity (binds on every device).** Each fused kernel
(ops/pallas_conv.fused_basic_block / fused_projection_block /
fused_bottleneck_block, interpret mode) must match the bitwise-pinned
Flax block — forward value, ALL input/parameter gradients, and every BN
batch-statistic pair. fp32 kinds bind at the exact-accumulation
tolerances (value/stats <= 3e-5 abs; grads 1e-4 rtol + 1e-3 atol). bf16
kinds compare the bf16 kernel against the SAME fp32 Flax reference at
the round-19 derived tolerances (docs/PERF.md round 19: bf16 unit
roundoff 2^-8 ~= 3.9e-3; observed worst value scaled-error 5.9e-3 and
worst grad cosine 0.9905 across kinds/geometries — ReLU-mask flips near
zero pre-activations make per-entry grad maxabs the wrong metric, so
grads bind on cosine): value scaled-maxabs <= 2e-2 AND cosine >= 0.9999;
grads cosine >= 0.95 AND scaled-maxabs <= 0.5; BN stats scaled-maxabs
<= 2e-2. ``parity_ok`` gates each kind's timing section: a timing number
for a kernel that computes the wrong thing is worthless.

**Timing (CPU-calibrated proxy).** On CPU the real HBM is not the
bottleneck and a TPU Pallas kernel cannot compile, so — exactly like
``resident_ab``/``window_ab`` model a serialized host link — this
proxy models the BANDWIDTH-BOUND regime the xplane evidence measured
(docs/PERF.md round 4: conv fusions at 69% of peak BW): both arms run
the SAME compiled block forward+backward step (arm math identical by
construction) and pay a fence + injected ``--hbm_delay_ms`` once per
modeled HBM traversal of the block's activation footprint, scaled by the
kind's ``bytes_scale`` (0.5 for bf16 — half the bytes per traversal is
the reason the bf16 kernels exist). The traversal counts are not free
parameters: the pallas counts are BlockSpec properties of
ops/pallas_conv.py (FWD/BWD_HBM_TRAVERSALS_{BLOCK,PROJ,BOTTLENECK} —
each stats phase re-reads its resident input tiles, outputs are written
once via the phase-gated index maps), and the xla counts follow the
round-4 fusion decomposition per kind (derivations in the
ops/pallas_conv.py constants and docs/PERF.md round 19). Arm order is
ABBA per round after one full discarded warm arm of each kind, and
every timed arm ends with a host readback of a COMPUTED scalar.

Expectation per kind: ``xla_ms - pallas_ms ~= delay * bytes_scale *
(T_xla - T_pallas)`` per step. The chip expectation derived from the
committed artifact lives in docs/PERF.md round 19, next to the honest
note that the end-to-end chip number is pending a chip-attached round.

Usage: python scripts/convblock_ab.py [--smoke] [--hbm_delay_ms N]
           [--rounds N] [--kinds basic proj ...] [--json OUT]
"""

import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from simclr_pytorch_distributed_tpu.models.resnet import (  # noqa: E402
    BasicBlock,
    Bottleneck,
)
from simclr_pytorch_distributed_tpu.ops import pallas_conv  # noqa: E402

SCHEMA = "convblock_ab/v2"
ARM_ORDER = ("xla", "pallas", "pallas", "xla")  # ABBA within every round

# fp32 parity tolerances (the tests' pins, restated for the artifact):
# fp32 accumulation-order noise between the shifted-matmul kernels and
# XLA's conv emitter
PARITY_VAL_TOL = 3e-5
PARITY_GRAD_RTOL = 1e-4
PARITY_GRAD_ATOL = 1e-3

# bf16 derived tolerances (docs/PERF.md round 19 derivation; the PR-3
# bf16-serving precedent of binding on agreement metrics, not bitwise)
BF16_VAL_SCALED_TOL = 2e-2
BF16_VAL_COS_FLOOR = 0.9999
BF16_GRAD_COS_FLOOR = 0.95
BF16_GRAD_SCALED_TOL = 0.5
BF16_STATS_SCALED_TOL = 2e-2

# per-kind modeled HBM traversals of the block's activation footprint per
# train step, each path — BlockSpec properties / round-4 decomposition
# (see the ops/pallas_conv.py constants' derivation comments)
TRAVERSALS = {
    "basic": {
        "xla": (pallas_conv.FWD_HBM_TRAVERSALS_XLA
                + pallas_conv.BWD_HBM_TRAVERSALS_XLA),
        "pallas": (pallas_conv.FWD_HBM_TRAVERSALS_BLOCK
                   + pallas_conv.BWD_HBM_TRAVERSALS_BLOCK),
    },
    "proj": {
        "xla": (pallas_conv.FWD_HBM_TRAVERSALS_PROJ_XLA
                + pallas_conv.BWD_HBM_TRAVERSALS_PROJ_XLA),
        "pallas": (pallas_conv.FWD_HBM_TRAVERSALS_PROJ
                   + pallas_conv.BWD_HBM_TRAVERSALS_PROJ),
    },
    "bottleneck": {
        "xla": (pallas_conv.FWD_HBM_TRAVERSALS_BOTTLENECK_XLA
                + pallas_conv.BWD_HBM_TRAVERSALS_BOTTLENECK_XLA),
        "pallas": (pallas_conv.FWD_HBM_TRAVERSALS_BOTTLENECK
                   + pallas_conv.BWD_HBM_TRAVERSALS_BOTTLENECK),
    },
}

BLOCK_KINDS = ("basic", "basic_bf16", "proj", "proj_bf16",
               "bottleneck", "bottleneck_bf16")


def _base_kind(kind):
    return kind[:-5] if kind.endswith("_bf16") else kind


def _dtype_tag(kind):
    return "bf16" if kind.endswith("_bf16") else "fp32"


def _bytes_scale(kind):
    # bf16 halves the bytes of every modeled activation traversal
    return 0.5 if kind.endswith("_bf16") else 1.0


def kind_geometry(kind, batch, size, channels):
    """Per-kind geometry derived from the three CLI knobs: the identity
    BasicBlock at (batch, size, channels), the projection block widening
    channels -> 2*channels at stride 2, the Bottleneck at planes=channels
    with a 2*channels input and a stride-2 projection shortcut (the new
    round-19 edges exercised where they differ most from round 15)."""
    base = _base_kind(kind)
    if base == "basic":
        return {"batch": batch, "h": size, "w": size,
                "in_channels": channels, "channels": channels, "stride": 1}
    if base == "proj":
        return {"batch": batch, "h": size, "w": size,
                "in_channels": channels, "channels": 2 * channels,
                "stride": 2}
    return {"batch": batch, "h": size, "w": size,
            "in_channels": 2 * channels, "planes": channels, "stride": 2}


def kind_supported(kind, geo):
    dtype = jnp.bfloat16 if _dtype_tag(kind) == "bf16" else jnp.float32
    base = _base_kind(kind)
    if base == "bottleneck":
        return pallas_conv.supports_bottleneck(
            geo["batch"], geo["h"], geo["w"], geo["planes"],
            stride=geo["stride"], in_channels=geo["in_channels"], dtype=dtype,
        )
    return pallas_conv.supports_block(
        geo["batch"], geo["h"], geo["w"], geo["channels"],
        stride=geo["stride"], in_channels=geo["in_channels"], dtype=dtype,
    )


def build_output(device, hbm_delay_ms, steps_per_arm, blocks):
    """Assemble the committed-artifact JSON from per-kind parity + round
    records (pure so tests pin the schema without running the
    measurement).

    ``blocks``: ``{kind: {"geometry", "dtype", "bytes_scale",
    "traversals", "parity", "runs"}}`` where runs is one dict per ABBA
    round, ``{"xla": [ms_per_step, ...], "pallas": [...]}`` (empty when
    that kind's parity is broken — timing for a wrong kernel is
    meaningless, but the artifact still carries the structured diffs)."""
    out_blocks = {}
    all_parity_ok = True
    for kind, b in blocks.items():
        runs = b.get("runs", [])
        all_xla = [v for r in runs for v in r["xla"]]
        all_pallas = [v for r in runs for v in r["pallas"]]
        xla_ms = statistics.median(all_xla) if all_xla else None
        pallas_ms = statistics.median(all_pallas) if all_pallas else None
        trav = b["traversals"]
        all_parity_ok = all_parity_ok and b["parity"]["parity_ok"]
        out_blocks[kind] = {
            "geometry": b["geometry"],
            "dtype": b["dtype"],
            "bytes_scale": b["bytes_scale"],
            "traversals": trav,
            "parity": b["parity"],
            "runs": runs,
            "summary": {
                "xla_ms_per_step": (
                    round(xla_ms, 2) if xla_ms is not None else None
                ),
                "pallas_ms_per_step": (
                    round(pallas_ms, 2) if pallas_ms is not None else None
                ),
                "traversal_removed_ms_per_step": (
                    round(xla_ms - pallas_ms, 2)
                    if xla_ms is not None and pallas_ms is not None else None
                ),
                "expected_removed_ms_per_step": round(
                    hbm_delay_ms * b["bytes_scale"]
                    * (trav["xla"] - trav["pallas"]), 2
                ),
                "speedup": (
                    round(xla_ms / pallas_ms, 3)
                    if xla_ms is not None and pallas_ms else None
                ),
            },
        }
    return {
        "schema": SCHEMA,
        "metric": "convblock_ab_ms_per_step",
        "hbm_delay_ms": hbm_delay_ms,
        "steps_per_arm": steps_per_arm,
        "arm_order": "ABBA per round: " + ",".join(ARM_ORDER),
        "blocks": out_blocks,
        "parity_ok": bool(all_parity_ok),
        "device": device,
        "note": (
            "paired CPU-proxy A/B per block kind: both arms run the SAME "
            "compiled block fwd+bwd step (arm math identical by "
            "construction; the kernel-vs-flax contract is each kind's "
            "parity section) and pay fence + injected delay once per "
            "modeled HBM traversal scaled by bytes_scale (0.5 for bf16) "
            "— per-materialization for the XLA fusion decomposition, "
            "per-phase-read/write for the fused kernels; each timed arm "
            "ends with a computed-scalar readback; per-kind parity_ok "
            "gates that kind's timing"
        ),
    }


def _compare(pairs, stats_pairs, dtype_tag):
    """Per-tensor comparison -> the artifact's parity dict. ``pairs``:
    [(name, pallas_val, flax_ref)] with 'out' first; ``stats_pairs``:
    [(name, pallas_stat, flax_ref_stat)]."""
    def cosine(a, b):
        a = np.asarray(a, np.float64).ravel()
        b = np.asarray(b, np.float64).ravel()
        return float(np.dot(a, b)
                     / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-30))

    diffs, metrics = {}, {}
    value_ok = grads_ok = stats_ok = True
    for name, a, b in pairs:
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        maxabs = float(np.max(np.abs(a - b)))
        diffs[name] = maxabs
        if dtype_tag == "fp32":
            if name == "out":
                value_ok = value_ok and maxabs <= PARITY_VAL_TOL
            else:
                bound = (PARITY_GRAD_ATOL
                         + PARITY_GRAD_RTOL * float(np.max(np.abs(b))))
                grads_ok = grads_ok and maxabs <= bound
        else:
            scaled = maxabs / (float(np.max(np.abs(b))) + 1e-30)
            co = cosine(a, b)
            metrics[name] = {"cos": round(co, 6),
                             "scaled_maxabs": round(scaled, 6)}
            if name == "out":
                value_ok = value_ok and (
                    scaled <= BF16_VAL_SCALED_TOL and co >= BF16_VAL_COS_FLOOR
                )
            else:
                grads_ok = grads_ok and (
                    co >= BF16_GRAD_COS_FLOOR
                    and scaled <= BF16_GRAD_SCALED_TOL
                )
    for name, a, b in stats_pairs:
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        maxabs = float(np.max(np.abs(a - b)))
        diffs[name] = maxabs
        if dtype_tag == "fp32":
            stats_ok = stats_ok and maxabs <= PARITY_VAL_TOL
        else:
            scaled = maxabs / (float(np.max(np.abs(b))) + 1e-30)
            metrics[name] = {"scaled_maxabs": round(scaled, 6)}
            stats_ok = stats_ok and scaled <= BF16_STATS_SCALED_TOL
    parity = {
        "parity_ok": bool(value_ok and grads_ok and stats_ok),
        "value_ok": bool(value_ok),
        "grads_ok": bool(grads_ok),
        "stats_ok": bool(stats_ok),
        "max_abs_diffs": {k: round(v, 9) for k, v in diffs.items()},
        "tolerances": (
            {"value_atol": PARITY_VAL_TOL, "grad_rtol": PARITY_GRAD_RTOL,
             "grad_atol": PARITY_GRAD_ATOL, "stats_atol": PARITY_VAL_TOL}
            if dtype_tag == "fp32" else
            {"value_scaled_maxabs": BF16_VAL_SCALED_TOL,
             "value_cos_floor": BF16_VAL_COS_FLOOR,
             "grad_cos_floor": BF16_GRAD_COS_FLOOR,
             "grad_scaled_maxabs": BF16_GRAD_SCALED_TOL,
             "stats_scaled_maxabs": BF16_STATS_SCALED_TOL}
        ),
    }
    if metrics:
        parity["bf16_metrics"] = metrics
    return parity


def measure_parity(kind, geo, seed=0):
    """Interpret-mode fused kernel vs the (always-fp32) Flax block for one
    kind: value, every gradient, every BN batch-stat pair."""
    from simclr_pytorch_distributed_tpu.models.norm import running_stats_update

    dtype_tag = _dtype_tag(kind)
    in_dtype = jnp.bfloat16 if dtype_tag == "bf16" else jnp.float32
    base = _base_kind(kind)
    rng = np.random.default_rng(seed)

    def arr(*shape, scale=1.0, shift=0.0):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32) * scale + shift
        )

    def loss_of(out):
        return jnp.sum(out * jnp.cos(out))

    n, h, w, stride = geo["batch"], geo["h"], geo["w"], geo["stride"]
    cin = geo["in_channels"]
    x = arr(n, h, w, cin)
    ho, wo = h // stride, w // stride

    if base in ("basic", "proj"):
        c = geo["channels"]
        k1 = arr(3, 3, cin, c, scale=0.2)
        g1, b1 = arr(c, shift=1.0), arr(c, scale=0.1)
        k2 = arr(3, 3, c, c, scale=0.2)
        g2, b2 = arr(c, shift=1.0), arr(c, scale=0.1)
        mod = BasicBlock(planes=c, stride=stride)
        params = {"Conv_0": {"kernel": k1}, "bn1": {"scale": g1, "bias": b1},
                  "Conv_1": {"kernel": k2}, "bn2": {"scale": g2, "bias": b2}}
        stats = {"bn1": {"mean": jnp.zeros(c), "var": jnp.ones(c)},
                 "bn2": {"mean": jnp.zeros(c), "var": jnp.ones(c)}}
        names = ["dx", "dk1", "dg1", "db1", "dk2", "dg2", "db2"]
        diff = [x, k1, g1, b1, k2, g2, b2]
        if base == "proj":
            ks = arr(1, 1, cin, c, scale=0.3)
            gs, bs = arr(c, shift=1.0), arr(c, scale=0.1)
            params["shortcut_conv"] = {"kernel": ks}
            params["shortcut_bn"] = {"scale": gs, "bias": bs}
            stats["shortcut_bn"] = {"mean": jnp.zeros(c), "var": jnp.ones(c)}
            names += ["dks", "dgs", "dbs"]
            diff += [ks, gs, bs]

        def rebuild(a):
            p = {"Conv_0": {"kernel": a[1]},
                 "bn1": {"scale": a[2], "bias": a[3]},
                 "Conv_1": {"kernel": a[4]},
                 "bn2": {"scale": a[5], "bias": a[6]}}
            if base == "proj":
                p["shortcut_conv"] = {"kernel": a[7]}
                p["shortcut_bn"] = {"scale": a[8], "bias": a[9]}
            return p

        def call_pal(*a):
            if base == "basic":
                return pallas_conv.fused_basic_block(
                    a[0].astype(in_dtype), *a[1:], interpret=True)
            return pallas_conv.fused_projection_block(
                a[0].astype(in_dtype), *a[1:], stride=stride, interpret=True)

        count = n * ho * wo if base == "proj" else n * h * w
        bn_moments = [("bn1", 1, 2, c, count), ("bn2", 3, 4, c, count)]
        if base == "proj":
            bn_moments.append(("shortcut_bn", 5, 6, c, count))
    else:  # bottleneck
        pln = geo["planes"]
        c4 = 4 * pln
        k1 = arr(1, 1, cin, pln, scale=0.3)
        g1, b1 = arr(pln, shift=1.0), arr(pln, scale=0.1)
        k2 = arr(3, 3, pln, pln, scale=0.2)
        g2, b2 = arr(pln, shift=1.0), arr(pln, scale=0.1)
        k3 = arr(1, 1, pln, c4, scale=0.3)
        g3, b3 = arr(c4, shift=1.0), arr(c4, scale=0.1)
        proj = stride != 1 or cin != c4
        mod = Bottleneck(planes=pln, stride=stride)
        params = {"Conv_0": {"kernel": k1}, "bn1": {"scale": g1, "bias": b1},
                  "Conv_1": {"kernel": k2}, "bn2": {"scale": g2, "bias": b2},
                  "Conv_2": {"kernel": k3}, "bn3": {"scale": g3, "bias": b3}}
        stats = {"bn1": {"mean": jnp.zeros(pln), "var": jnp.ones(pln)},
                 "bn2": {"mean": jnp.zeros(pln), "var": jnp.ones(pln)},
                 "bn3": {"mean": jnp.zeros(c4), "var": jnp.ones(c4)}}
        names = ["dx", "dk1", "dg1", "db1", "dk2", "dg2", "db2",
                 "dk3", "dg3", "db3"]
        diff = [x, k1, g1, b1, k2, g2, b2, k3, g3, b3]
        if proj:
            ks = arr(1, 1, cin, c4, scale=0.3)
            gs, bs = arr(c4, shift=1.0), arr(c4, scale=0.1)
            params["shortcut_conv"] = {"kernel": ks}
            params["shortcut_bn"] = {"scale": gs, "bias": bs}
            stats["shortcut_bn"] = {"mean": jnp.zeros(c4), "var": jnp.ones(c4)}
            names += ["dks", "dgs", "dbs"]
            diff += [ks, gs, bs]

        def rebuild(a):
            p = {"Conv_0": {"kernel": a[1]},
                 "bn1": {"scale": a[2], "bias": a[3]},
                 "Conv_1": {"kernel": a[4]},
                 "bn2": {"scale": a[5], "bias": a[6]},
                 "Conv_2": {"kernel": a[7]},
                 "bn3": {"scale": a[8], "bias": a[9]}}
            if proj:
                p["shortcut_conv"] = {"kernel": a[10]}
                p["shortcut_bn"] = {"scale": a[11], "bias": a[12]}
            return p

        def call_pal(*a):
            sc = (a[10], a[11], a[12]) if proj else None
            return pallas_conv.fused_bottleneck_block(
                a[0].astype(in_dtype), a[1], a[2], a[3], a[4], a[5], a[6],
                a[7], a[8], a[9], sc, stride=stride, interpret=True)

        count1, count2 = n * h * w, n * ho * wo
        bn_moments = [("bn1", 1, 2, pln, count1), ("bn2", 3, 4, pln, count2),
                      ("bn3", 5, 6, c4, count2)]
        if proj:
            bn_moments.append(("shortcut_bn", 7, 8, c4, count2))

    def flax_out(*a):
        out, mut = mod.apply(
            {"params": rebuild(a), "batch_stats": stats}, a[0], True,
            mutable=["batch_stats"],
        )
        return out, mut["batch_stats"]

    argnums = tuple(range(len(diff)))
    r = call_pal(*diff)
    out_ref, stats_ref = flax_out(*diff)
    gp = jax.grad(
        lambda *a: loss_of(call_pal(*a)[0].astype(jnp.float32)),
        argnums=argnums,
    )(*diff)
    gr = jax.grad(lambda *a: loss_of(flax_out(*a)[0]), argnums=argnums)(*diff)

    pairs = [("out", r[0].astype(jnp.float32), out_ref)]
    pairs += list(zip(names, gp, gr))
    stats_pairs = []
    for bn_name, mi, vi, cc, cnt in bn_moments:
        ra_m, ra_v = running_stats_update(
            jnp.zeros(cc), jnp.ones(cc), r[mi], r[vi], cnt, 0.1
        )
        stats_pairs.append(
            (f"{bn_name}_mean", ra_m, stats_ref[bn_name]["mean"]))
        stats_pairs.append(
            (f"{bn_name}_var", ra_v, stats_ref[bn_name]["var"]))
    return _compare(pairs, stats_pairs, dtype_tag)


def make_train_step(kind, geo, seed=1):
    """One compiled block fwd+bwd 'step' for the timing arms: loss over
    the Flax block output, grads to the two 3x3/central conv kernels,
    tiny SGD-ish update — BOTH arms run exactly this program (the proxy's
    treatment is the traversal count x bytes_scale)."""
    base = _base_kind(kind)
    rng = np.random.default_rng(seed)
    n, h, w, stride = geo["batch"], geo["h"], geo["w"], geo["stride"]
    cin = geo["in_channels"]
    x0 = jnp.asarray(rng.standard_normal((n, h, w, cin)).astype(np.float32))

    def arr(*shape, scale=1.0):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32) * scale)

    if base in ("basic", "proj"):
        c = geo["channels"]
        mod = BasicBlock(planes=c, stride=stride)
        ka = arr(3, 3, cin, c, scale=0.2)
        kb = arr(3, 3, c, c, scale=0.2)

        def make_params(kk1, kk2):
            p = {"Conv_0": {"kernel": kk1},
                 "bn1": {"scale": jnp.ones(c), "bias": jnp.zeros(c)},
                 "Conv_1": {"kernel": kk2},
                 "bn2": {"scale": jnp.ones(c), "bias": jnp.zeros(c)}}
            s = {"bn1": {"mean": jnp.zeros(c), "var": jnp.ones(c)},
                 "bn2": {"mean": jnp.zeros(c), "var": jnp.ones(c)}}
            if base == "proj":
                p["shortcut_conv"] = {"kernel": arr(1, 1, cin, c, scale=0.3)}
                p["shortcut_bn"] = {"scale": jnp.ones(c),
                                    "bias": jnp.zeros(c)}
                s["shortcut_bn"] = {"mean": jnp.zeros(c), "var": jnp.ones(c)}
            return p, s
    else:
        pln = geo["planes"]
        c4 = 4 * pln
        mod = Bottleneck(planes=pln, stride=stride)
        ka = arr(1, 1, cin, pln, scale=0.3)
        kb = arr(3, 3, pln, pln, scale=0.2)

        def make_params(kk1, kk2):
            p = {"Conv_0": {"kernel": kk1},
                 "bn1": {"scale": jnp.ones(pln), "bias": jnp.zeros(pln)},
                 "Conv_1": {"kernel": kk2},
                 "bn2": {"scale": jnp.ones(pln), "bias": jnp.zeros(pln)},
                 "Conv_2": {"kernel": arr(1, 1, pln, c4, scale=0.3)},
                 "bn3": {"scale": jnp.ones(c4), "bias": jnp.zeros(c4)},
                 "shortcut_conv": {"kernel": arr(1, 1, cin, c4, scale=0.3)},
                 "shortcut_bn": {"scale": jnp.ones(c4),
                                 "bias": jnp.zeros(c4)}}
            s = {"bn1": {"mean": jnp.zeros(pln), "var": jnp.ones(pln)},
                 "bn2": {"mean": jnp.zeros(pln), "var": jnp.ones(pln)},
                 "bn3": {"mean": jnp.zeros(c4), "var": jnp.ones(c4)},
                 "shortcut_bn": {"mean": jnp.zeros(c4), "var": jnp.ones(c4)}}
            return p, s

    @jax.jit
    def train_step(kk1, kk2):
        def loss(kk1, kk2):
            p, s = make_params(kk1, kk2)
            out, _ = mod.apply(
                {"params": p, "batch_stats": s}, x0, True,
                mutable=["batch_stats"],
            )
            return jnp.mean(jnp.square(out))

        l, (dk1, dk2) = jax.value_and_grad(loss, argnums=(0, 1))(kk1, kk2)
        return l, kk1 - 1e-3 * dk1, kk2 - 1e-3 * dk2

    return train_step, ka, kb


def main(argv=None):
    def positive_int(s):
        v = int(s)
        if v < 1:
            raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
        return v

    def nonneg_float(s):
        v = float(s)
        if v < 0:
            raise argparse.ArgumentTypeError(f"must be >= 0, got {v}")
        return v

    ap = argparse.ArgumentParser()
    ap.add_argument("--hbm_delay_ms", type=nonneg_float, default=None,
                    help="injected per-traversal delay; default 5 ms, 10 ms "
                         "under --smoke (the injected stall must dominate "
                         "the tiny-block compute so the effect clears "
                         "1-core timer/contention noise — the window_ab "
                         "convention)")
    ap.add_argument("--steps", type=positive_int, default=None,
                    help="timed steps per arm; default 8, 2 under --smoke")
    ap.add_argument("--rounds", type=positive_int, default=2,
                    help="ABBA rounds (2 measurements per arm per round)")
    ap.add_argument("--batch", type=positive_int, default=None,
                    help="block batch rows; default 32, 16 under --smoke")
    ap.add_argument("--size", type=positive_int, default=None,
                    help="spatial side; default 16, 8 under --smoke")
    ap.add_argument("--channels", type=positive_int, default=None,
                    help="base block width (kind_geometry derives the "
                         "proj/bottleneck shapes); default 16, 8 under "
                         "--smoke")
    ap.add_argument("--kinds", nargs="+", choices=BLOCK_KINDS,
                    default=list(BLOCK_KINDS),
                    help="block-kind sections to run; default all six")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU config for tests and the committed-"
                         "artifact run")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    # --smoke fills only flags the caller left unset (flush_ab pattern)
    smoke_defaults = dict(batch=16, size=8, channels=8, steps=2,
                          hbm_delay_ms=10.0)
    full_defaults = dict(batch=32, size=16, channels=16, steps=8,
                         hbm_delay_ms=5.0)
    for k, v in (smoke_defaults if args.smoke else full_defaults).items():
        if getattr(args, k) is None:
            setattr(args, k, v)

    delay_s = args.hbm_delay_ms / 1e3
    blocks = {}
    any_parity_broken = False
    for kind in args.kinds:
        geo = kind_geometry(kind, args.batch, args.size, args.channels)
        if not kind_supported(kind, geo):
            raise SystemExit(f"{kind}: geometry {geo} not admitted")
        base = _base_kind(kind)
        trav = TRAVERSALS[base]
        scale = _bytes_scale(kind)

        # ---- parity (gates this kind's timing, before any timing) -------
        parity = measure_parity(kind, geo)
        print(json.dumps({"kind": kind, "parity": parity}), flush=True)
        entry = {"geometry": geo, "dtype": _dtype_tag(kind),
                 "bytes_scale": scale, "traversals": trav,
                 "parity": parity, "runs": []}
        blocks[kind] = entry
        if not parity["parity_ok"]:
            any_parity_broken = True
            continue

        # ---- timing -----------------------------------------------------
        train_step, kk1, kk2 = make_train_step(kind, geo)

        def run_arm(mode, kk1, kk2):
            t0 = time.perf_counter()
            for _ in range(args.steps):
                # serialized-link model (resident_ab/window_ab convention):
                # a bandwidth-bound chip pays its HBM time serially with
                # compute — fence the in-flight step, then pay one
                # bytes-scaled delay per modeled traversal of the
                # activation footprint
                jax.block_until_ready((kk1, kk2))
                for _ in range(trav[mode]):
                    time.sleep(delay_s * scale)
                l, kk1, kk2 = train_step(kk1, kk2)
            # honest sync: a computed scalar cannot exist until the steps
            # ran
            assert np.isfinite(float(l))
            dt = time.perf_counter() - t0
            return kk1, kk2, dt * 1e3 / args.steps

        # warmup: compile + ONE FULL DISCARDED ARM OF EACH KIND
        kk1, kk2, warm_x = run_arm("xla", kk1, kk2)
        kk1, kk2, warm_p = run_arm("pallas", kk1, kk2)
        print(json.dumps({"kind": kind, "warmup_discarded_ms_per_step":
                          {"xla": round(warm_x, 2),
                           "pallas": round(warm_p, 2)}}), flush=True)

        for rnd in range(args.rounds):
            record = {"xla": [], "pallas": []}
            for mode in ARM_ORDER:
                kk1, kk2, ms = run_arm(mode, kk1, kk2)
                record[mode].append(round(ms, 2))
                print(json.dumps({"kind": kind, "round": rnd, "arm": mode,
                                  "ms_per_step": round(ms, 2)}), flush=True)
            entry["runs"].append(record)

    out = build_output(
        jax.devices()[0].device_kind, args.hbm_delay_ms, args.steps, blocks,
    )
    print(json.dumps(out))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    if any_parity_broken:
        raise SystemExit("parity BROKEN: timing would be meaningless")
    return out


if __name__ == "__main__":
    main()
