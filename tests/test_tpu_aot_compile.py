"""The chip's compiler, asked without the chip (on-chip-measurement guide §2).

libtpu compiles for a *described* ``v5e:2x2`` topology: nothing runs, so
these tests say only whether Mosaic/XLA:TPU accept the main path's kernels at
the launcher's geometry (``run_supcon.sh``: rn50, batch 256 -> 512 view rows,
32x32). Interpret-mode parity tests cannot see a VMEM overflow; these can.

The refused conv combinations are ``xfail(strict=True)``: they are why
``--conv_impl auto`` resolves to ``xla`` (ROADMAP A1). Whoever repairs or
deletes a kernel kind has to touch its case here.

Rules this file keeps (libtpu is loaded by ONE process, and pytest-xdist
workers each import every test file): the topology is described inside a
module-scoped fixture, never at import; every compile runs in the test's own
process; the persistent compilation cache is off around the compiles (an
entry written for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from simclr_pytorch_distributed_tpu.ops import pallas_conv, pallas_loss

ROWS, SIZE, FEAT_DIM = 512, 32, 128  # 2 * batch 256 view rows, CIFAR, head out


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data_mesh(topo):
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel is in the program
    return text


def _maybe_grad(fn, grad: bool):
    return jax.grad(fn) if grad else fn


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_fused_loss_compiles_single_chip(one_chip, grad):
    feats = jax.ShapeDtypeStruct(
        (ROWS // 2, 2, FEAT_DIM), jnp.float32, sharding=one_chip
    )
    _compile(
        _maybe_grad(
            lambda f: pallas_loss.fused_supcon_loss(
                f, temperature=0.5, base_temperature=0.07
            ),
            grad,
        ),
        feats,
    )


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_sharded_fused_loss_compiles_on_four_chip_mesh(data_mesh, grad):
    """B=256 on the ``data=4`` mesh, as train/supcon_step.py calls it."""
    feats = jax.ShapeDtypeStruct(
        (ROWS, FEAT_DIM), jnp.float32,
        sharding=NamedSharding(data_mesh, P("data")),
    )
    loss = shard_map(
        lambda rows: pallas_loss.fused_sharded_supcon_loss(
            rows, None, axis_name="data", temperature=0.5,
            base_temperature=0.07, n_views=2,
        ),
        mesh=data_mesh, in_specs=P("data"), out_specs=P(), check_vma=False,
    )
    text = _compile(_maybe_grad(loss, grad), feats)
    assert "all-gather" in text  # the contrast side is gathered over 'data'


def _conv_program(kind: str, dtype, sharding):
    """``(fn, arg_shapes)``: the kernel entry point reduced to a scalar, at
    the rn50 launcher geometry of its first site."""

    def sds(*shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def bn(c):
        return [sds(c), sds(c)]

    if kind == "stem":  # 3 -> 64
        entry = pallas_conv.fused_conv_bn_relu
        args = [sds(ROWS, SIZE, SIZE, 3, dt=dtype), sds(3, 3, 3, 64), *bn(64)]
    elif kind == "basic":  # identity 64 -> 64
        entry = pallas_conv.fused_basic_block
        args = [sds(ROWS, SIZE, SIZE, 64, dt=dtype),
                sds(3, 3, 64, 64), *bn(64), sds(3, 3, 64, 64), *bn(64)]
    else:  # identity bottleneck 256 -> 64 -> 256
        entry = pallas_conv.fused_bottleneck_block
        args = [sds(ROWS, SIZE, SIZE, 256, dt=dtype),
                sds(1, 1, 256, 64), *bn(64), sds(3, 3, 64, 64), *bn(64),
                sds(1, 1, 64, 256), *bn(256)]

    def scalar(*a):
        return jnp.sum(entry(*a)[0].astype(jnp.float32))

    return scalar, args


# What Mosaic refuses today (scoped VMEM over the 16 MiB limit), although the
# ``supports_*`` gates admit it: the verdict table of CHANGES.md, PR 23.
REFUSED = {
    ("stem", "float32", "fwd"), ("stem", "float32", "grad"),
    ("stem", "bfloat16", "grad"),
    ("basic", "float32", "grad"),
    ("bottleneck", "float32", "grad"),
}
CONV_CASES = [
    pytest.param(
        kind, dtype, mode, id=f"{kind}-{dtype}-{mode}",
        marks=[pytest.mark.xfail(
            strict=True, raises=jax.errors.JaxRuntimeError,
            reason="Mosaic refuses it: scoped VMEM over 16 MiB (ROADMAP A1)",
        )] if (kind, dtype, mode) in REFUSED else [],
    )
    for kind in ("stem", "basic", "bottleneck")
    for dtype in ("float32", "bfloat16")
    for mode in ("fwd", "grad")
]


@pytest.mark.parametrize("kind,dtype,mode", CONV_CASES)
def test_fused_conv_kernel_compiles(one_chip, kind, dtype, mode):
    """Every case here is one ``supports_*`` ADMITS at this geometry."""
    fn, args = _conv_program(kind, jnp.dtype(dtype), one_chip)
    if mode == "grad":
        fn = jax.grad(fn, argnums=tuple(range(len(args))))
    _compile(fn, *args)
