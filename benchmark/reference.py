"""Plain reference of the pretrain step: what the configuration states, written
straight down in ``jax.numpy`` and float32.

It imports nothing of the program and takes nothing the program has made. It
makes the weights itself from the seed (``init_params``; the harness hands the
same arrays to the program through ``adapter.py``), augments the same uint8
rows with the same keys, runs encoder, batch-statistics BN with its running
statistics, head, NT-Xent, gradients and SGD with momentum, and returns what
``compare.py`` holds the program's first three steps against. Every product
(convolutions, head, similarities, and the crop's two interpolation products)
is taken at the device's default precision, as the configurations state.

The augmentation's random draws are part of the configuration: a view's key
is split four ways (crop, flip, jitter, grayscale), the crop's three ways
(ten areas, ten log-ratios, the corner), the jitter's gate two ways and its
body five ways (order, brightness, contrast, saturation, hue). Every discrete
choice comes from the key alone, so two sound implementations differ only by
rounding.
"""

from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

WIDTHS = (64, 128, 256, 512)
STRIDES = (1, 2, 2, 2)
ARCHS = {
    # model -> (block kind, blocks per stage, channel expansion)
    "resnet18": ("basic", (2, 2, 2, 2), 1),
    "resnet50": ("bottleneck", (3, 4, 6, 3), 4),
}
BN_EPS = 1e-5


def block_plan(model: str):
    """The residual blocks in order: dicts of name, kind, cin, planes, stride,
    cout and whether the shortcut is a projection."""
    kind, stages, expansion = ARCHS[model]
    plan, cin = [], 64
    for s, (n, width, stride) in enumerate(zip(stages, WIDTHS, STRIDES)):
        for b in range(n):
            st = stride if b == 0 else 1
            cout = width * expansion
            plan.append({
                "name": f"layer{s + 1}.{b}", "kind": kind, "cin": cin,
                "planes": width, "stride": st, "cout": cout,
                "project": st != 1 or cin != cout,
            })
            cin = cout
    return plan


def feature_dim(model: str) -> int:
    return WIDTHS[-1] * ARCHS[model][2]


def conv_list(model: str, size: int):
    """Every convolution of one forward pass: dicts of name, k, cin, cout,
    stride, hin (input side), hout. ``flops.py`` counts from this list."""
    convs = [{"name": "stem/conv", "k": 3, "cin": 3, "cout": 64, "stride": 1,
              "hin": size, "hout": size}]
    h = size
    for blk in block_plan(model):
        ho = -(-h // blk["stride"])
        n, p = blk["name"], blk["planes"]
        if blk["kind"] == "basic":
            convs.append({"name": f"{n}/conv1", "k": 3, "cin": blk["cin"],
                          "cout": p, "stride": blk["stride"], "hin": h, "hout": ho})
            convs.append({"name": f"{n}/conv2", "k": 3, "cin": p, "cout": p,
                          "stride": 1, "hin": ho, "hout": ho})
        else:
            convs.append({"name": f"{n}/conv1", "k": 1, "cin": blk["cin"],
                          "cout": p, "stride": 1, "hin": h, "hout": h})
            convs.append({"name": f"{n}/conv2", "k": 3, "cin": p, "cout": p,
                          "stride": blk["stride"], "hin": h, "hout": ho})
            convs.append({"name": f"{n}/conv3", "k": 1, "cin": p,
                          "cout": blk["cout"], "stride": 1, "hin": ho, "hout": ho})
        if blk["project"]:
            convs.append({"name": f"{n}/shortcut/conv", "k": 1, "cin": blk["cin"],
                          "cout": blk["cout"], "stride": blk["stride"],
                          "hin": h, "hout": ho})
        h = ho
    return convs


def stats_order(model: str):
    """The names of BN's running statistics in the order of the forward pass."""
    return [f"{c['name'].replace('conv', 'bn')}/{s}" for c in conv_list(model, 32)
            for s in ("mean", "var")]


def param_spec(model: str, feat_dim: int = 128):
    """name -> (shape, init) for every trainable array. Inits: ``he`` is a
    normal of variance 2 / (k*k*cout), ``one``/``zero`` are BN's scale and
    bias, ``lin<fan_in>`` is uniform within 1/sqrt(fan_in) (the reference
    recipe's torch defaults)."""
    spec = {}

    def bn(prefix, c):
        spec[f"{prefix}/scale"] = ((c,), "one")
        spec[f"{prefix}/bias"] = ((c,), "zero")

    for c in conv_list(model, 32):
        spec[c["name"]] = ((c["k"], c["k"], c["cin"], c["cout"]), "he")
        bn(c["name"].replace("conv", "bn"), c["cout"])
    f = feature_dim(model)
    spec["head/fc1/w"] = ((f, f), f"lin{f}")
    spec["head/fc1/b"] = ((f,), f"lin{f}")
    spec["head/fc2/w"] = ((f, feat_dim), f"lin{f}")
    spec["head/fc2/b"] = ((feat_dim,), f"lin{f}")
    return spec


def init_params(key, model: str, feat_dim: int = 128):
    """All weights from one key, in float32: one normal draw cut up over the
    convolutions and one uniform draw over the linear layers, so that the
    whole of it is one small program on the device."""
    spec = sorted(param_spec(model, feat_dim).items())
    count = lambda kind: sum(  # noqa: E731
        math.prod(shape) for _, (shape, init) in spec if init.startswith(kind))
    normal = jax.random.normal(jax.random.fold_in(key, 0), (count("he"),), jnp.float32)
    uniform = jax.random.uniform(jax.random.fold_in(key, 1), (count("lin"),), jnp.float32,
                                 -1.0, 1.0)
    params, n_at, u_at = {}, 0, 0
    for name, (shape, init) in spec:
        size = math.prod(shape)
        if init == "he":
            std = math.sqrt(2.0 / (shape[0] * shape[1] * shape[3]))
            params[name] = std * normal[n_at:n_at + size].reshape(shape)
            n_at += size
        elif init == "one":
            params[name] = jnp.ones(shape, jnp.float32)
        elif init == "zero":
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            bound = 1.0 / math.sqrt(int(init[3:]))
            params[name] = bound * uniform[u_at:u_at + size].reshape(shape)
            u_at += size
    return params


# ------------------------------------------------------------ augmentation


def _interp_matrix(coords, n):
    """[out, n] bilinear weights: row i holds the two hat weights of the
    sample at ``coords[i]``; at the border the two taps coincide and add."""
    c = jnp.clip(coords, 0.0, n - 1.0)
    c0 = jnp.floor(c)
    frac = c - c0
    i0 = jnp.clip(c0.astype(jnp.int32), 0, n - 1)
    i1 = jnp.clip(i0 + 1, 0, n - 1)
    grid = jnp.arange(n)[None, :]
    return ((grid == i0[:, None]) * (1.0 - frac)[:, None]
            + (grid == i1[:, None]) * frac[:, None])


def _crop_resize(img, top, left, h, w, size, precision=None):
    """Bilinear resize of the crop box to size x size, half-pixel centres,
    samples clamped to the box: two products with the interpolation matrices,
    rows first and then columns, at the precision the configuration states
    for every product. On the TPU that rounds pixels and weights to bfloat16,
    which is what the configuration's step does to them (PERF.md, "How
    correct is decided": with the interpolation taken exactly in float32
    instead, sound runs read as far from the reference as the control)."""
    d = jnp.arange(size, dtype=jnp.float32)
    ys = jnp.clip(top + (d + 0.5) * (h / size) - 0.5, top, top + h - 1.0)
    xs = jnp.clip(left + (d + 0.5) * (w / size) - 0.5, left, left + w - 1.0)
    wy = _interp_matrix(ys, img.shape[0])
    wx = _interp_matrix(xs, img.shape[1])
    rows = jnp.einsum("sh,hwc->swc", wy, img, precision=precision)
    return jnp.einsum("xw,swc->sxc", wx, rows, precision=precision)


def _random_resized_crop(key, img, size, precision=None, scale=(0.2, 1.0),
                         ratio=(3.0 / 4.0, 4.0 / 3.0), attempts=10):
    H, W = img.shape[0], img.shape[1]
    k_area, k_ratio, k_ij = jax.random.split(key, 3)
    area = float(H * W) * jax.random.uniform(
        k_area, (attempts,), minval=scale[0], maxval=scale[1])
    aspect = jnp.exp(jax.random.uniform(
        k_ratio, (attempts,), minval=math.log(ratio[0]), maxval=math.log(ratio[1])))
    ws = jnp.round(jnp.sqrt(area * aspect))
    hs = jnp.round(jnp.sqrt(area / aspect))
    valid = (ws > 0) & (ws <= W) & (hs > 0) & (hs <= H)
    first = jnp.argmax(valid)
    ok = jnp.any(valid)
    # square sources only: the fallback is the whole image
    w = jnp.where(ok, ws[first], float(W))
    h = jnp.where(ok, hs[first], float(H))
    u_top, u_left = jax.random.uniform(k_ij, (2,))
    top = jnp.where(ok, jnp.floor(u_top * (H - h + 1)), jnp.round((H - h) / 2.0))
    left = jnp.where(ok, jnp.floor(u_left * (W - w + 1)), jnp.round((W - w) / 2.0))
    return _crop_resize(img, top, left, h, w, size, precision)


def _luma(img):
    return jnp.sum(img * jnp.array([0.299, 0.587, 0.114], img.dtype),
                   axis=-1, keepdims=True)


def _hue(img, delta):
    r, g, b = img[..., 0], img[..., 1], img[..., 2]
    maxc = jnp.maximum(jnp.maximum(r, g), b)
    minc = jnp.minimum(jnp.minimum(r, g), b)
    v, c = maxc, maxc - minc
    s = jnp.where(maxc > 0, c / jnp.maximum(maxc, 1e-12), 0.0)
    sc = jnp.maximum(c, 1e-12)
    rc, gc, bc = (maxc - r) / sc, (maxc - g) / sc, (maxc - b) / sc
    h = jnp.where(r == maxc, bc - gc,
                  jnp.where(g == maxc, 2.0 + rc - bc, 4.0 + gc - rc))
    h = jnp.where(c == 0, 0.0, (h / 6.0) % 1.0)
    h = (h + delta) % 1.0
    i = jnp.floor(h * 6.0)
    f = h * 6.0 - i
    p, q, t = v * (1.0 - s), v * (1.0 - s * f), v * (1.0 - s * (1.0 - f))
    i = i.astype(jnp.int32) % 6
    pick = lambda *xs: jnp.select([i == n for n in range(6)], xs)  # noqa: E731
    return jnp.stack([pick(v, q, p, p, t, v), pick(t, v, v, q, p, p),
                      pick(p, p, t, v, v, q)], axis=-1)


def _color_jitter(key, img, strength=(0.4, 0.4, 0.4, 0.1)):
    k_perm, k_b, k_c, k_s, k_h = jax.random.split(key, 5)
    b, c, s, hue = strength
    fb = jax.random.uniform(k_b, (), minval=1 - b, maxval=1 + b)
    fc = jax.random.uniform(k_c, (), minval=1 - c, maxval=1 + c)
    fs = jax.random.uniform(k_s, (), minval=1 - s, maxval=1 + s)
    fh = jax.random.uniform(k_h, (), minval=-hue, maxval=hue)
    order = jax.random.permutation(k_perm, 4)
    for n in range(4):
        img = jnp.select(
            [order[n] == 0, order[n] == 1, order[n] == 2, order[n] == 3],
            [jnp.clip(img * fb, 0.0, 1.0),
             jnp.clip(fc * img + (1.0 - fc) * jnp.mean(_luma(img)), 0.0, 1.0),
             jnp.clip(fs * img + (1.0 - fs) * _luma(img), 0.0, 1.0),
             _hue(img, fh)])
    return img


def _one_view(key, img_u8, size, mean, std, precision=None):
    img = img_u8.astype(jnp.float32) / 255.0
    k_crop, k_flip, k_jit, k_gray = jax.random.split(key, 4)
    img = _random_resized_crop(k_crop, img, size, precision)
    img = jnp.where(jax.random.bernoulli(k_flip, 0.5), img[:, ::-1, :], img)
    k_gate, k_body = jax.random.split(k_jit)
    img = jnp.where(jax.random.bernoulli(k_gate, 0.8), _color_jitter(k_body, img), img)
    gray = jnp.broadcast_to(_luma(img), img.shape)
    img = jnp.where(jax.random.bernoulli(k_gray, 0.2), gray, img)
    return (img - jnp.asarray(mean, jnp.float32)) / jnp.asarray(std, jnp.float32)


def two_views(key, images_u8, size, mean, std, precision=None):
    """[B,H,W,3] uint8 -> [2B,size,size,3] float32, all first views then all
    second views; image b takes keys 2b and 2b+1 of ``split(key, 2B)``."""
    B = images_u8.shape[0]
    keys = jax.random.split(key, 2 * B).reshape(B, 2)
    view = partial(_one_view, size=size, mean=mean, std=std, precision=precision)
    v1 = jax.vmap(view)(keys[:, 0], images_u8)
    v2 = jax.vmap(view)(keys[:, 1], images_u8)
    return jnp.concatenate([v1, v2], axis=0)


# ------------------------------------------------------------------ model


def _conv(x, w, stride):
    pad = (w.shape[0] - 1) // 2
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, prefix, stats):
    """Train-mode batch norm over the whole (global) batch: SyncBN. Notes the
    batch's mean and its unbiased variance, which the running statistics
    take, under ``<prefix>/mean`` and ``<prefix>/var`` in ``stats``."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    count = x.shape[0] * x.shape[1] * x.shape[2]
    stats[f"{prefix}/mean"] = mean
    stats[f"{prefix}/var"] = var * (count / (count - 1))
    return ((x - mean) * jax.lax.rsqrt(var + BN_EPS) * p[f"{prefix}/scale"]
            + p[f"{prefix}/bias"])


def forward(p, x, model: str):
    """Encoder and projection head: [N,H,W,3] -> ([N,feat_dim] unnormalised,
    every BN's batch statistics)."""
    stats = {}
    cb = lambda x, name, stride: _bn(  # noqa: E731
        _conv(x, p[name], stride), p, name.replace("conv", "bn"), stats)
    x = jax.nn.relu(cb(x, "stem/conv", 1))
    for blk in block_plan(model):
        n, s = blk["name"], blk["stride"]
        if blk["kind"] == "basic":
            out = jax.nn.relu(cb(x, f"{n}/conv1", s))
            out = cb(out, f"{n}/conv2", 1)
        else:
            out = jax.nn.relu(cb(x, f"{n}/conv1", 1))
            out = jax.nn.relu(cb(out, f"{n}/conv2", s))
            out = cb(out, f"{n}/conv3", 1)
        short = cb(x, f"{n}/shortcut/conv", s) if blk["project"] else x
        x = jax.nn.relu(out + short)
    x = jnp.mean(x, axis=(1, 2))
    h = jax.nn.relu(jnp.dot(x, p["head/fc1/w"]) + p["head/fc1/b"])
    return jnp.dot(h, p["head/fc2/w"]) + p["head/fc2/b"], stats


def nt_xent(feats, temperature, base_temperature):
    """SimCLR's loss over [2B,D] rows laid out view-major, every row an
    anchor, with the recipe's ``temperature / base_temperature`` scale."""
    n = feats.shape[0]
    z = feats / jnp.linalg.norm(feats, axis=1, keepdims=True)
    logits = jnp.dot(z, z.T) / temperature
    idx = jnp.arange(n)
    not_self = idx[:, None] != idx[None, :]
    log_denom = jax.nn.logsumexp(jnp.where(not_self, logits, -jnp.inf), axis=1)
    pos = logits[idx, (idx + n // 2) % n]
    return -(temperature / base_temperature) * jnp.mean(pos - log_denom)


# --------------------------------------------------------------- training


def learning_rate(step: int, hp: dict) -> float:
    """The recipe's schedule at a 0-based global step: cosine by epoch, and in
    the first ``warm_epochs`` a linear ramp by step that overrides it."""
    lr, rate, epochs = hp["learning_rate"], hp["lr_decay_rate"], hp["epochs"]
    eta_min = lr * rate ** 3
    cos = lambda e: eta_min + (lr - eta_min) * (1 + math.cos(math.pi * e / epochs)) / 2  # noqa: E731
    epoch = step // hp["steps_per_epoch"] + 1
    if hp["warm"] and epoch <= hp["warm_epochs"]:
        p = step / (hp["warm_epochs"] * hp["steps_per_epoch"])
        return hp["warmup_from"] + p * (cos(hp["warm_epochs"]) - hp["warmup_from"])
    return cos(epoch)


def make_step(model: str, hp: dict, resize_precision=None, drop_half: bool = False):
    """One training step as a pure function ``(params, momentum, running,
    images_u8, key, lr) -> (params, momentum, running, loss, grads)``;
    ``running`` holds BN's running statistics.

    Every product of the step is taken at the device's default precision,
    which is what the configurations state. Two arguments serve the readings
    that ``control.py`` takes and nothing else: ``drop_half`` plants the fault
    "half of the batch left out, the mean taken over the rest", and
    ``resize_precision="highest"`` takes the crop's interpolation exactly in
    float32 while everything else stays as stated.
    """

    def loss_fn(p, views):
        if drop_half:
            b = views.shape[0] // 2
            views = jnp.concatenate([views[: b // 2], views[b: b + b // 2]])
        feats, stats = forward(p, views, model)
        loss = nt_xent(feats, hp["temp"], hp["base_temperature"])
        return loss / hp["grad_div"], (loss, stats)

    def step(params, mom, running, images_u8, key, lr):
        views = two_views(key, images_u8, hp["size"], hp["mean"], hp["std"], resize_precision)
        grads, (loss, stats) = jax.grad(loss_fn, has_aux=True)(params, views)
        new_mom = jax.tree.map(
            lambda m, g, p: hp["momentum"] * m + g + hp["weight_decay"] * p,
            mom, grads, params)
        new_params = jax.tree.map(lambda p, m: p - lr * m, params, new_mom)
        m = hp["bn_momentum"]
        new_running = {k: (1.0 - m) * running[k] + m * stats[k] for k in running}
        return new_params, new_mom, new_running, loss, grads

    return step


def init_running(params):
    """BN's running statistics before the first step: mean 0, variance 1."""
    running = {}
    for name, scale in params.items():
        if name.endswith("/scale"):
            prefix = name[: -len("/scale")]
            running[f"{prefix}/mean"] = jnp.zeros_like(scale)
            running[f"{prefix}/var"] = jnp.ones_like(scale)
    return running


@functools.lru_cache(maxsize=None)
def _programs(model, hp_items, resize_precision, drop_half, shardings):
    """The jitted step and its two helpers; kept, so that a process that
    follows several seeds traces each variant once."""
    one = make_step(model, dict(hp_items), resize_precision, drop_half)

    def step(params, mom, running, batches, key, k, lr):
        # the key is an argument: closed over, it would be a constant of the
        # program and every seed would compile anew
        return one(params, mom, running, batches[k], jax.random.fold_in(key, k), lr)

    start = lambda p: (jax.tree.map(jnp.zeros_like, p), init_running(p))  # noqa: E731
    minus = lambda a, b: jax.tree.map(jnp.subtract, a, b)  # noqa: E731
    if shardings is None:
        return jax.jit(step), jax.jit(start), jax.jit(minus)
    repl, rows = shardings
    return (jax.jit(step, in_shardings=(repl, repl, repl, rows, repl, repl, repl),
                    out_shardings=repl),
            jax.jit(start, out_shardings=repl), jax.jit(minus, out_shardings=repl))


def trajectory(params, batches_u8, base_key, model, hp, steps=3, resize_precision=None,
               drop_half=False, shardings=None):
    """Follow the first ``steps`` steps from ``params``. Returns the losses
    (host floats) and, as dicts of arrays on the device: ``grad``, the first
    gradient; ``change``, the parameters' change over all the steps; ``stats``,
    the change of BN's running statistics over the first step, with their names
    in the order of the forward pass under ``stats_order``.

    ``batches_u8`` is [steps, B, H, W, 3] uint8. ``shardings``, a pair
    (replicated, rows of a [steps, B, ...] array split over the chips),
    spreads the same plain program over several chips where one cannot hold
    the global batch.
    """
    jstep, jstart, jminus = _programs(model, tuple(sorted(hp.items())), resize_precision,
                                      drop_half, shardings)
    mom, running0 = jstart(params)
    p, running, losses, grad, stats = params, running0, [], None, None
    for k in range(steps):
        p, mom, running, loss, grads = jstep(p, mom, running, batches_u8, base_key, np.int32(k),
                                             np.float32(learning_rate(k, hp)))
        losses.append(loss)
        if k == 0:
            grad, stats = grads, jminus(running, running0)
        del grads
    return {"losses": [float(v) for v in jax.device_get(losses)],
            "grad": grad, "change": jminus(p, params), "stats": stats,
            "stats_order": stats_order(model)}
