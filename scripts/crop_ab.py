#!/usr/bin/env python
"""Crop-as-matmul vs per-pixel-gather A/B under the honest-sync methodology.

Round 1 claimed "+17% end-to-end from expressing crop+resize as two small
interpolation matmuls instead of a per-pixel gather", but that number was
measured under the broken ``block_until_ready`` sync and docs/PERF.md has
carried it as **unverified** since round 2. This script settles it on the
real chip with the honest methodology (chained iterations inside ONE
``fori_loop`` dispatch, computed-scalar readback, median of windows,
dispatch floor subtracted — see scripts/_honest_timing.py for why a
python loop of dispatches could not resolve sub-ms programs in round 5).

Two levels:

- **kernel**: ``ops.augment.crop_and_resize`` (the production path — two
  dense interpolation matmuls that batch onto the MXU under vmap,
  ``ops/augment.py:61-84``) vs a semantics-identical bilinear gather
  (4 advanced-indexing taps + lerp, the way a GPU port would write it,
  mirroring the host-side PIL crop the reference uses,
  ``/root/reference/main_supcon.py:170-179``). Numerics are asserted equal
  (<=1e-5) before any timing.
- **pipeline**: the full ``two_crop_batch`` contrastive aug program (crop,
  flip, jitter, grayscale, normalize for 2 views x batch) with each crop
  backend monkeypatched in — the aug stack as the train step actually
  traces it.

Usage:  python scripts/crop_ab.py [--batch 256] [--json OUT]
"""

import argparse
import contextlib
import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _honest_timing import time_per_iter  # noqa: E402
from simclr_pytorch_distributed_tpu.ops import augment  # noqa: E402

SIZE = 32


def crop_and_resize_gather(img, top, left, h, w, out_size):
    """Bilinear crop+resize via per-pixel gathers — semantics match
    ``augment.crop_and_resize`` exactly (same half-pixel centers, same
    crop-box clamping, same border replication), only the lowering differs:
    4 gather taps + lerp instead of two interpolation matmuls."""
    H, W = img.shape[0], img.shape[1]
    d = jnp.arange(out_size, dtype=jnp.float32)
    ys = top + (d + 0.5) * (h / out_size) - 0.5
    xs = left + (d + 0.5) * (w / out_size) - 0.5
    ys = jnp.clip(jnp.clip(ys, top, top + h - 1.0), 0.0, H - 1.0)
    xs = jnp.clip(jnp.clip(xs, left, left + w - 1.0), 0.0, W - 1.0)
    y0 = jnp.floor(ys)
    x0 = jnp.floor(xs)
    fy = (ys - y0)[:, None, None]
    fx = (xs - x0)[None, :, None]
    y0i = jnp.clip(y0.astype(jnp.int32), 0, H - 1)
    y1i = jnp.clip(y0i + 1, 0, H - 1)
    x0i = jnp.clip(x0.astype(jnp.int32), 0, W - 1)
    x1i = jnp.clip(x0i + 1, 0, W - 1)
    v00 = img[y0i[:, None], x0i[None, :]]
    v01 = img[y0i[:, None], x1i[None, :]]
    v10 = img[y1i[:, None], x0i[None, :]]
    v11 = img[y1i[:, None], x1i[None, :]]
    return (
        v00 * (1 - fy) * (1 - fx)
        + v01 * (1 - fy) * fx
        + v10 * fy * (1 - fx)
        + v11 * fy * fx
    )


def _rand_params(key, batch, H=32, W=32):
    """Random crop boxes shaped like RandomResizedCrop draws (area 0.2-1.0)."""
    k1, k2, k3 = jax.random.split(key, 3)
    hw = jnp.round(
        jnp.sqrt(jax.random.uniform(k1, (batch,), minval=0.2, maxval=1.0))
        * H
    )
    hw = jnp.clip(hw, 1.0, float(H))
    u = jax.random.uniform(k2, (batch, 2))
    top = jnp.floor(u[:, 0] * (H - hw + 1))
    left = jnp.floor(u[:, 1] * (W - hw + 1))
    return top, left, hw, hw


def _check_numerics(batch):
    key = jax.random.key(7)
    imgs = jax.random.uniform(jax.random.key(1), (batch, 32, 32, 3))
    top, left, h, w = _rand_params(key, batch)
    vmat = jax.vmap(lambda im, t, l, hh, ww: augment.crop_and_resize(
        im, t, l, hh, ww, SIZE))
    b = jax.vmap(lambda im, t, l, hh, ww: crop_and_resize_gather(
        im, t, l, hh, ww, SIZE))(imgs, top, left, h, w)
    # semantic equality: the matmul path at full precision IS the gather
    with jax.default_matmul_precision("highest"):
        a_hi = vmat(imgs, top, left, h, w)
    err_hi = float(jnp.max(jnp.abs(a_hi - b)))
    assert err_hi <= 1e-5, f"gather crop diverges from matmul crop: {err_hi}"
    # at TPU default precision the einsums round through bf16 — report the
    # deviation the production path actually carries (images live in [0,1])
    err_default = float(jnp.max(jnp.abs(vmat(imgs, top, left, h, w) - b)))
    return err_hi, err_default


def _kernel_core(crop_fn):
    vcrop = jax.vmap(lambda im, t, l, hh, ww: crop_fn(im, t, l, hh, ww, SIZE))

    def core(i, imgs, base_key):
        key = jax.random.fold_in(base_key, i)
        top, left, h, w = _rand_params(key, imgs.shape[0])
        out = vcrop(imgs, top, left, h, w)
        return jnp.sum(out) * 1e-20

    return core


@contextlib.contextmanager
def _patched_crop(crop_fn):
    """Swap the production crop backend for the whole timing call.

    The monkeypatch must bracket EVERY compilation of the timed program, not
    just the first trace: patching inside the traced core only works while
    that exact trace is live, and any re-trace (a jit cache miss from new
    input avals, a second harness window) would silently time the wrong
    backend (ADVICE.md round 5). Patching around ``time_per_iter`` — which
    owns all compiles of its looped/single programs — closes that hole.
    """
    saved = augment.crop_and_resize
    augment.crop_and_resize = crop_fn
    try:
        yield
    finally:
        augment.crop_and_resize = saved


def _pipeline_core(crop_fn):
    cfg = augment.AugmentConfig()

    def core(i, imgs, base_key):
        key = jax.random.fold_in(base_key, i)
        # crop_fn reaches two_crop_batch via the module global, patched at
        # the make_core level (_patched_crop around the whole timing call)
        assert augment.crop_and_resize is crop_fn, "time under _patched_crop"
        out = augment.two_crop_batch(key, imgs, cfg)
        return jnp.sum(out) * 1e-20

    return core


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters_kernel", type=int, default=500)
    ap.add_argument("--iters_pipeline", type=int, default=100)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    err_hi, err_default = _check_numerics(args.batch)
    base_key = jax.random.key(0)
    imgs_f = jax.random.uniform(jax.random.key(1), (args.batch, 32, 32, 3))
    # pipeline input follows the [0,255] value convention; the carrier stays
    # float so the harness's chained perturbation composes (to_float yields
    # bit-identical normalized pixels either way, and H2D transfer — where
    # uint8 matters — is outside every timed window)
    imgs_255 = imgs_f * 255.0

    records = []
    for level, make_core, iters, inputs, needs_patch in (
        ("crop_kernel", _kernel_core, args.iters_kernel, imgs_f, False),
        ("two_crop_pipeline", _pipeline_core, args.iters_pipeline, imgs_255, True),
    ):
        def timed(crop_fn):
            # pipeline level: the patch brackets every compile inside
            # time_per_iter (see _patched_crop); the kernel level calls
            # crop_fn directly and needs no patch
            ctx = _patched_crop(crop_fn) if needs_patch else contextlib.nullcontext()
            with ctx:
                return time_per_iter(make_core(crop_fn), (inputs, base_key), iters)

        matmul_s = timed(augment.crop_and_resize)
        gather_s = timed(crop_and_resize_gather)
        rec = {
            "metric": f"crop_ab_{level}_ms",
            "batch": args.batch,
            "matmul_ms": round(matmul_s * 1e3, 4),
            "gather_ms": round(gather_s * 1e3, 4),
            "gather_over_matmul": (
                round(gather_s / matmul_s, 2) if matmul_s > 0 else None
            ),
            "numeric_max_abs_diff_highest_precision": err_hi,
            "numeric_max_abs_diff_default_precision": err_default,
            "device": jax.devices()[0].device_kind,
        }
        records.append(rec)
        print(json.dumps(rec), flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)


if __name__ == "__main__":
    main()
