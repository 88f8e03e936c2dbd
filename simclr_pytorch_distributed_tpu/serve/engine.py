"""EmbeddingEngine — checkpoint -> eval-mode encoder behind a shape-bucketed
jit cache.

The serving problem with jit is shape polymorphism: every distinct batch
shape is a fresh trace + XLA compile (seconds on CPU, tens of seconds for
big models on TPU), so letting arbitrary request sizes reach the compiled
function turns the first request of every new size into a multi-second
outlier. The fix is a small set of power-of-two batch **buckets**
(default 1/8/32/128): a request of n images is padded up to the smallest
bucket >= n, the compiled program for that bucket runs, and the pad rows are
sliced off the result. Requests larger than the top bucket are chunked
through it.

Why padding is sound: in eval mode (``train=False``) every per-example path
is batch-independent — BN reads running statistics, convs/pools/matmuls are
per-row — so row i's embedding does not depend on rows != i. Within one
compiled program this holds **bitwise** (pad rows, real rows, their count:
irrelevant); across different bucket programs XLA may schedule reductions
differently, so two buckets agree only to float tolerance (~1 ulp observed
on CPU). Both halves of that contract are pinned by
``tests/test_eval_determinism.py`` / ``tests/test_serve_engine.py``.

Device placement goes through ``parallel/mesh.py``: params are replicated,
and a bucket whose size divides the mesh's data axis is sharded across it
(the same data-parallel layout the trainers use — more chips means bigger
buckets at the same latency); smaller buckets run replicated.

The optional ``cache`` (serve/cache.py) sits in FRONT of the compiled call:
rows whose content hash hits skip engine execution entirely, and a request
made entirely of hits never touches the device.

**Dispatch/completion split.** jax dispatches jitted calls asynchronously:
the call returns a device array the moment the work is ENQUEUED, and only
``np.asarray`` (D2H) blocks on it. The training loop already exploits this
(docs/PERF.md: a per-step sync cost 2.4x wall clock); serving gets the same
split here. ``dispatch(images) -> InflightBatch`` runs the host stages —
validation, cache probe, bucket padding, H2D via
``parallel.mesh.put_batch_if_divisible`` — and enqueues the compiled call
for EVERY bucket chunk without materializing anything;
``InflightBatch.result()`` is the completion stage: it blocks on D2H,
slices pad rows, and populates the cache. ``embed`` is now literally
``dispatch(...).result()``, so a miss set spanning several bucket chunks
overlaps chunk k+1's dispatch with chunk k's compute instead of
round-tripping each chunk, and the DynamicBatcher keeps several whole
batches in flight by holding their ``InflightBatch`` handles
(serve/batcher.py).

**bf16 serving** (``dtype="bf16"``): params and activations are cast to
bfloat16 at load — the same bf16-on-MXU win the trainers take with
``--bf16`` — while BN statistics stay fp32 (models/norm.py normalizes in
fp32 regardless of compute dtype) and the head output is cast back to fp32,
so the wire contract is unchanged. Parity with fp32 serving is pinned by
``tests/test_serve_engine.py`` the same way ``tests/test_eval_determinism.py``
pins the fp32 contract.
"""

from __future__ import annotations

import hashlib
import threading
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from simclr_pytorch_distributed_tpu.models import (
    SupConResNet,
    infer_architecture_from_variables,
)
from simclr_pytorch_distributed_tpu.ops.augment import (
    DATASET_STATS,
    AugmentConfig,
    eval_batch,
)
from simclr_pytorch_distributed_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding_if_divisible,
    create_mesh,
    put_batch_if_divisible,
    replicated_sharding,
)
from simclr_pytorch_distributed_tpu.utils import tracing

DEFAULT_BUCKETS = (1, 8, 32, 128)
SERVE_DTYPES = ("fp32", "bf16")


class InflightBatch:
    """Handle to dispatched-but-unmaterialized engine work.

    Created by :meth:`EmbeddingEngine.dispatch` after every bucket chunk's
    compiled call has been ENQUEUED on the device; ``result()`` is the
    completion stage — it blocks on the D2H transfers, slices the pad rows
    off, writes computed rows into the content cache, and returns the
    float32 ``[n, dim]`` array. Idempotent: repeat calls return the same
    array without touching the device again. The handle owns device buffers
    until completed, which is exactly what the batcher's in-flight row
    bound counts (serve/batcher.py ``max_inflight_images``).
    """

    def __init__(self, engine, out, n, chunks, keys):
        self._engine = engine
        self._out = out
        self._n = n
        self._chunks = chunks  # [(miss row indices, device array)]
        self._keys = keys
        self._done = False
        self._lock = threading.Lock()

    @property
    def n_rows(self) -> int:
        """Total request rows (the batcher's HBM-bound accounting unit)."""
        return self._n

    def done(self) -> bool:
        with self._lock:
            return self._done

    def result(self) -> np.ndarray:
        with self._lock:
            if not self._done:
                cache = self._engine.cache
                for rows, dev in self._chunks:
                    emb = np.asarray(dev)[: len(rows)]  # blocks on D2H
                    self._out[rows] = emb
                    if self._keys is not None:
                        cache.put_many(
                            [(self._keys[i], emb[j]) for j, i in enumerate(rows)]
                        )
                self._chunks = ()  # release device buffers
                self._done = True
            return self._out


class EmbeddingEngine:
    """Batched eval-mode embedding inference over a frozen encoder.

    ``embed(images) -> np.ndarray``: uint8 NHWC images in, float32
    ``[n, dim]`` embeddings out. ``output='features'`` serves the encoder's
    pooled features (the probe/kNN/retrieval representation,
    ``SupConResNet.encode``); ``output='projection'`` serves the projection
    head's output. ``normalize=True`` L2-normalizes rows to match the
    post-gather contract the contrastive loss consumes (``ops/losses.py``
    expects unit rows; the reference normalizes at ``main_supcon.py:283``).
    """

    def __init__(
        self,
        model: SupConResNet,
        variables: dict,
        *,
        mesh=None,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        normalize: bool = False,
        output: str = "features",
        mean: Optional[Tuple[float, ...]] = None,
        std: Optional[Tuple[float, ...]] = None,
        img_size: int = 32,
        cache=None,
        dtype: str = "fp32",
        identity: str = "",
    ):
        if output not in ("features", "projection"):
            raise ValueError(f"output must be features|projection, got {output!r}")
        if dtype not in SERVE_DTYPES:
            raise ValueError(f"dtype must be one of {SERVE_DTYPES}, got {dtype!r}")
        self.dtype = dtype
        if dtype == "bf16":
            # params + activations cast to bf16 at load (halved param HBM,
            # MXU-native compute — the trainers' --bf16 win); BN statistics
            # stay fp32 (models/norm.py normalizes in fp32 regardless of
            # compute dtype) and _apply casts the head output back to fp32
            model = model.clone(dtype=jnp.bfloat16)
            variables = dict(variables)
            variables["params"] = jax.tree.map(
                lambda x: jnp.asarray(x, jnp.bfloat16)
                if jnp.issubdtype(x.dtype, np.floating) else x,
                variables["params"],
            )
        buckets = tuple(sorted(int(b) for b in buckets))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"buckets must be positive, got {buckets}")
        if len(set(buckets)) != len(buckets):
            raise ValueError(f"duplicate buckets: {buckets}")
        self.model = model
        self.buckets = buckets
        self.normalize = bool(normalize)
        self.output = output
        # pinned request geometry: the bucket scheme bounds compiles only if
        # the SPATIAL shape is fixed too — an open endpoint accepting
        # arbitrary (H, W) would compile per size (multi-second outliers,
        # unbounded executable cache: a trivial DoS). Mismatches are
        # rejected in validate_images (HTTP 400, never a compile).
        self.img_size = int(img_size)
        self.cache = cache
        stats = DATASET_STATS["cifar10"]
        self._aug_cfg = AugmentConfig(
            mean=tuple(mean) if mean else stats[0],
            std=tuple(std) if std else stats[1],
            color_ops=False,
        )
        self.mesh = mesh if mesh is not None else create_mesh()
        self._repl = replicated_sharding(self.mesh)
        self._variables = jax.device_put(variables, self._repl)
        if output == "features":
            self.feat_dim = model.encoder_dim
        else:
            self.feat_dim = model.feat_dim
        self._jit_fns: dict = {}  # sharded vs replicated jit objects
        self._lock = threading.Lock()
        self._stats = {
            "requests": 0,
            "images": 0,
            "padded_rows": 0,
            "bucket_dispatches": {b: 0 for b in buckets},
            "traces": {},  # bucket -> trace count (compile-count witness)
            "cache_hit_rows": 0,
        }
        # cache-key fingerprint: byte-identical images served under a
        # different contract — another normalization/output, OR another
        # model/checkpoint (EmbeddingCache is injectable, so one cache may
        # back several engines) — must never share a cache row. The weights
        # probe hashes EVERY leaf: a single canonical leaf won't do (tree
        # order puts BN statistics first, which are identical zeros/ones
        # across fresh checkpoints). One-time cost at construction.
        probe = hashlib.sha1()
        for leaf in jax.tree.leaves(variables):
            probe.update(np.asarray(leaf).tobytes())
        self._weights_probe = probe.hexdigest()[:16]
        self.identity = ""
        self.set_identity(identity)

    def set_identity(self, identity: str) -> None:
        """Stamp the engine's served identity (``"<model name>@v<version>"``)
        into its cache-key fingerprint.

        The weights probe already separates engines whose *weights* differ,
        but a hot-swap promotion must invalidate cached rows even when the
        new version's weights happen to be byte-identical (a re-exported or
        rolled-back checkpoint): after ``POST /models/promote`` every hit
        must come from the version that is actually serving. The registry
        (serve/fleet/registry.py) stamps ``name@vN`` BEFORE the version
        becomes visible to traffic — this is not safe to call with requests
        in flight (``_cache_key`` reads the prefix without a lock)."""
        self.identity = str(identity)
        self._key_prefix = (
            f"{self.identity}|{self.model.model_name}|{self._weights_probe}|"
            f"{self.output}|{int(self.normalize)}|{self.dtype}|"
            f"{self._aug_cfg.mean}|{self._aug_cfg.std}|".encode()
        )

    # ------------------------------------------------------------ loading

    @classmethod
    def from_checkpoint(cls, path: str, **kwargs) -> "EmbeddingEngine":
        """Build from any ``--ckpt`` spelling: an orbax checkpoint dir, a run
        dir (latest complete checkpoint wins), or a reference ``.pth``
        (converted in place on first use). The architecture is inferred from
        the restored tree itself — no ``--model`` flag needed."""
        from simclr_pytorch_distributed_tpu.utils.checkpoint import (
            load_model_payload,
        )

        variables, meta = load_model_payload(path)
        name, head, feat_dim = infer_architecture_from_variables(variables)
        model = SupConResNet(model_name=name, head=head, feat_dim=feat_dim)
        config = meta.get("config") or {}
        dataset = config.get("dataset")
        if (dataset in DATASET_STATS and "mean" not in kwargs
                and "std" not in kwargs):
            kwargs["mean"], kwargs["std"] = DATASET_STATS[dataset]
        # pin the geometry the encoder was trained at (checkpoint meta
        # records the training config's --size) unless the caller overrides
        if "img_size" not in kwargs and config.get("size"):
            kwargs["img_size"] = int(config["size"])
        return cls(model, dict(variables), **kwargs)

    @classmethod
    def random_init(
        cls, model_name: str = "resnet10", size: int = 32, seed: int = 0, **kwargs
    ) -> "EmbeddingEngine":
        """Randomly initialized engine — benchmarking and tests (the serving
        stack's behavior is weight-independent)."""
        model = SupConResNet(model_name=model_name)
        variables = model.init(
            jax.random.key(seed), jnp.zeros((2, size, size, 3)), train=False
        )
        kwargs.setdefault("img_size", size)
        return cls(
            model,
            {"params": variables["params"], "batch_stats": variables["batch_stats"]},
            **kwargs,
        )

    # ------------------------------------------------------------ compute

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (requests above the top bucket are chunked
        through it)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _apply(self, variables, images_u8):
        # NOTE: body executes at TRACE time only — the counter bump below is
        # the compile witness the no-recompile tests assert on. It runs in
        # ordinary Python (tracing, not compiled code), so it takes the lock:
        # an unlocked insert racing a /stats dict copy can crash the poll.
        bucket = int(images_u8.shape[0])
        with self._lock:
            self._stats["traces"][bucket] = (
                self._stats["traces"].get(bucket, 0) + 1
            )
        x = eval_batch(images_u8, self._aug_cfg)
        if self.output == "features":
            feats = self.model.apply(
                variables, x, train=False, method=SupConResNet.encode
            )
        else:
            feats = self.model.apply(variables, x, train=False)
        feats = feats.astype(jnp.float32)
        if self.normalize:
            norms = jnp.linalg.norm(feats, axis=-1, keepdims=True)
            feats = feats / jnp.maximum(norms, 1e-12)
        return feats

    def _fn_for(self, bucket: int):
        # Two jit objects, picked by whether the bucket shards evenly over
        # the data axis; each caches one executable per bucket shape.
        sharded = bucket % self.mesh.shape.get(DATA_AXIS, 1) == 0
        with self._lock:
            fn = self._jit_fns.get(sharded)
            if fn is None:
                fn = jax.jit(
                    self._apply,
                    in_shardings=(
                        self._repl,
                        batch_sharding_if_divisible(self.mesh, bucket, 4),
                    ),
                    out_shardings=self._repl,
                )
                self._jit_fns[sharded] = fn
        return fn

    def _dispatch_chunk(self, images_u8: np.ndarray) -> jax.Array:
        """Pad to the bucket, start the H2D transfer, enqueue the compiled
        call — and return the UNmaterialized device array. Everything here
        is the dispatch stage; the only blocking step (D2H) belongs to
        ``InflightBatch.result``."""
        n = images_u8.shape[0]
        bucket = self.bucket_for(n)
        padded = images_u8
        if n < bucket:
            padded = np.zeros((bucket,) + images_u8.shape[1:], np.uint8)
            padded[:n] = images_u8
        with self._lock:
            self._stats["bucket_dispatches"][bucket] += 1
            self._stats["padded_rows"] += bucket - n
        x = put_batch_if_divisible(self.mesh, padded)
        return self._fn_for(bucket)(self._variables, x)

    def _cache_key(self, image_u8: np.ndarray) -> bytes:
        h = hashlib.sha1(self._key_prefix)
        h.update(str(image_u8.shape).encode())
        h.update(image_u8.tobytes())
        return h.digest()

    def validate_images(self, images: np.ndarray) -> np.ndarray:
        """Raise ``ValueError`` unless ``images`` matches the engine's pinned
        request geometry. Exposed separately so ingress layers (the
        batcher's ``validate=``, hence the HTTP 400 path) can reject bad
        requests synchronously instead of poisoning a coalesced batch."""
        images = np.asarray(images)
        if images.ndim != 4 or images.shape[-1] != 3:
            raise ValueError(
                f"expected [n, H, W, 3] images, got shape {images.shape}"
            )
        if images.shape[1:3] != (self.img_size, self.img_size):
            raise ValueError(
                f"this engine serves {self.img_size}x{self.img_size} images "
                f"(pinned at construction; arbitrary sizes would compile per "
                f"shape), got {images.shape[1]}x{images.shape[2]}"
            )
        if images.dtype != np.uint8:
            raise ValueError(
                f"expected uint8 images (raw pixels; the engine normalizes), "
                f"got {images.dtype}"
            )
        return images

    def dispatch(self, images: np.ndarray) -> InflightBatch:
        """Start one request's device work without waiting for it.

        Runs every host-side stage — validation, stats, cache probe, bucket
        padding, H2D — and enqueues the compiled call for ALL bucket chunks
        of the miss set (a multi-bucket request overlaps chunk k+1's
        dispatch with chunk k's compute instead of round-tripping each).
        The returned :class:`InflightBatch` completes with ``result()``;
        until then the device computes while the caller assembles the next
        batch (serve/batcher.py keeps ``max_inflight`` of these on device).
        """
        images = self.validate_images(images)
        n = images.shape[0]
        out = np.empty((n, self.feat_dim), np.float32)
        if n == 0:
            return InflightBatch(self, out, 0, [], None)
        with self._lock:
            self._stats["requests"] += 1
            self._stats["images"] += n

        if self.cache is None:
            miss_rows = list(range(n))
            keys = None
        else:
            keys = [self._cache_key(images[i]) for i in range(n)]
            miss_rows = []
            for i, key in enumerate(keys):
                row = self.cache.get(key)
                if row is None:
                    miss_rows.append(i)
                else:
                    out[i] = row
            hit_rows = n - len(miss_rows)
            if hit_rows:
                with self._lock:
                    self._stats["cache_hit_rows"] += hit_rows
                # the cache leg of the request path: rows that never reach
                # the device (a full-hit request has an empty miss set and
                # dispatches nothing)
                tracing.event(
                    "cache_hits", track="serve:cache", rows=hit_rows, n=n
                )

        chunks = []
        max_bucket = self.buckets[-1]
        for lo in range(0, len(miss_rows), max_bucket):
            rows = miss_rows[lo:lo + max_bucket]
            chunks.append((rows, self._dispatch_chunk(images[rows])))
        return InflightBatch(self, out, n, chunks, keys)

    def embed(self, images: np.ndarray) -> np.ndarray:
        """uint8 ``[n, H, W, 3]`` -> float32 ``[n, feat_dim]``.

        Row i's embedding depends only on image i — never on which request
        peers or pad rows it was batched with — so micro-batching and the
        content cache are transparent to callers. Synchronous spelling of
        ``dispatch(...).result()``.
        """
        return self.dispatch(images).result()

    # -------------------------------------------------------------- stats

    def stats(self) -> dict:
        with self._lock:
            s = {
                **{k: v for k, v in self._stats.items()
                   if not isinstance(v, dict)},
                "bucket_dispatches": dict(self._stats["bucket_dispatches"]),
                "traces": dict(self._stats["traces"]),
            }
        s["model"] = self.model.model_name
        s["identity"] = self.identity
        s["output"] = self.output
        s["normalize"] = self.normalize
        s["dtype"] = self.dtype
        s["buckets"] = list(self.buckets)
        s["feat_dim"] = self.feat_dim
        if self.cache is not None:
            s["cache"] = self.cache.stats()
        return s
