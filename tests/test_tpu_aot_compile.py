"""The chip's compiler, asked without the chip (on-chip-measurement guide §2).

libtpu compiles for a *described* ``v5e:2x2`` topology: nothing runs, so
these tests say only whether Mosaic/XLA:TPU accept the main path's kernels at
the launcher's geometry (``run_supcon.sh``: rn50, batch 256 -> 512 view rows,
32x32). Interpret-mode parity tests cannot see a VMEM overflow; these can.

Rules this file keeps (libtpu is loaded by ONE process, and pytest-xdist
workers each import every test file): the topology is described inside a
module-scoped fixture, never at import; every compile runs in the test's own
process; the persistent compilation cache is off around the compiles (an
entry written for a described chip cannot be read back without one).
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from jax import lax

from simclr_pytorch_distributed_tpu.models import experts
from simclr_pytorch_distributed_tpu.models import sparse_attention as attention_layer
from simclr_pytorch_distributed_tpu.models import token_encoder
from simclr_pytorch_distributed_tpu.ops import (
    delta_rule, pallas_loss, pointwise_bwd, short_conv, sparse_attention)

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


# ---- Bottleneck's tail on one backward kernel (ops/pointwise_bwd.py): the
# stage geometries of rn50 at the launcher's 512 rows, and other batches


def _bottleneck_stack(x, blocks):
    """Identity Bottlenecks in plain jnp, the tail routed as
    models/resnet.py routes it; reduced to a scalar."""
    eps = 1e-5

    stats = pointwise_bwd.batch_moments

    def bn(t, scale, bias):
        mean, var = stats(t)
        return (t - mean) * lax.rsqrt(var + eps) * scale + bias

    def conv(t, w, padding):
        return lax.conv_general_dilated(
            t, w, (1, 1), padding, dimension_numbers=("NHWC", "HWIO", "NHWC")
        )

    for p in blocks:
        out = jax.nn.relu(bn(conv(x, p["w1"], "VALID"), p["s1"], p["b1"]))
        z = conv(out, p["w2"], ((1, 1), (1, 1)))
        mean2, var2 = stats(z)
        y, _, _ = pointwise_bwd.expand_conv_bn(
            z, mean2, lax.rsqrt(var2 + eps), p["s2"], p["b2"], p["w3"],
            p["s3"], p["b3"], eps=eps, interpret=False,
        )
        x = jax.nn.relu(y + x)
    return jnp.sum(jnp.square(x))


_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?)\s([\w\-]+)\((.*)$"
)
_THROUGH = ("bitcast", "reshape", "get-tuple-element")


def _elements(result_type: str) -> int:
    """Elements of the largest array in an instruction's result type."""
    return max((math.prod(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"\[([\d,]*)\]", result_type)), default=1)


def _kernel_neighbours(text):
    """``[(opcode, elements)]`` of what feeds and what follows each Mosaic
    call in the entry computation, seen through bitcasts, reshapes and tuple
    elements (which move no bytes)."""
    entry = text[text.index("\nENTRY"):]
    ops = {}
    for line in entry.splitlines():
        m = _HLO_LINE.match(line)
        if not m:
            continue
        name, shape, opcode, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest.split("),", 1)[0])
        ops[name] = (opcode, _elements(shape), operands,
                     "tpu_custom_call" in line)
    users = {}
    for name, (_, _, operands, _) in ops.items():
        for operand in operands:
            users.setdefault(operand, []).append(name)

    def walk(name, step):
        for other in step(name):
            if other not in ops:
                continue
            if ops[other][0] in _THROUGH:
                yield from walk(other, step)
            else:
                yield ops[other][:2]

    found = []
    for name, (_, _, operands, is_kernel) in ops.items():
        if is_kernel:
            found += list(walk(name, lambda n: ops[n][2]))
            found += list(walk(name, lambda n: users.get(n, [])))
    return found


def _tail_kernel_shapes(sharding, rows, planes, positions=2):
    """Operands of ``pointwise_bwd._backward_call`` as ``_bwd`` hands them
    over (VMEM is per grid step: the number of positions does not enter)."""

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    wide = 4 * planes
    gv, dy = sds(3, 1, wide), sds(positions, rows, wide)
    if pointwise_bwd.rows_minor(planes):
        return (gv, sds(4, planes, 1), sds(planes, wide, dtype=jnp.bfloat16),
                dy, dy, sds(positions, planes, rows))
    return (gv, sds(4, 1, planes), sds(wide, planes, dtype=jnp.bfloat16),
            dy, dy, sds(positions, rows, planes))


@pytest.mark.parametrize("rows", [128, 256, 384, 400, 512, 768, 1024, 1040, 2048])
def test_pointwise_bwd_admits_only_what_mosaic_accepts(one_chip, rows):
    """Whatever ``unsupported`` lets through at some batch, Mosaic compiles:
    the step of a run at that batch cannot die in the compiler (the parent
    trained at every one of these). 512 rows are the benchmark's; the others
    are batches 64 to 1024 and two that are no power of two times 128."""
    admitted = [
        planes for planes in (64, 128, 256, 512)
        if pointwise_bwd.unsupported(rows, planes, 4 * planes) is None
    ]
    if rows == ROWS:
        assert admitted == [64, 128, 256]  # stage 4 needs 26 MiB a block
    for planes in admitted:
        _compile(
            lambda *a, minor=pointwise_bwd.rows_minor(planes):
                pointwise_bwd._backward_call(*a, minor=minor, interpret=False),
            *_tail_kernel_shapes(one_chip, rows, planes),
        )


def test_pointwise_bwd_budget_is_the_compilers(one_chip):
    """The shape rule is not guesswork: stage 4 at the launcher's 512 rows,
    which ``unsupported`` leaves on XLA's path, is refused by Mosaic for the
    VMEM the rule counts."""
    assert "26.0 MiB of VMEM" in pointwise_bwd.unsupported(ROWS, 512, 2048)
    with pytest.raises(Exception, match=r"(?i)vmem.*26\.04M"):
        _compile(
            lambda *a: pointwise_bwd._backward_call(*a, minor=False, interpret=False),
            *_tail_kernel_shapes(one_chip, ROWS, 512),
        )


@pytest.mark.parametrize("size,planes", [(32, 64), (16, 128), (8, 256)],
                         ids=lambda v: str(v))
def test_pointwise_bwd_compiles_with_no_layout_copy(one_chip, size, planes):
    """Mosaic accepts the kernel at the geometry of each stage that takes it
    at 512 rows, float32, under ``jax.grad`` of a two-block stack, and XLA
    puts no copy or transpose of activation size before or after it: the
    operand orders of ops/pointwise_bwd.py are bitcasts of what the conv
    fusions choose. (``copy-start`` / ``copy-done`` pairs are not layout
    changes: the compiler's prefetches into fast memory, which its own
    fusions get too.)"""

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    wide = 4 * planes
    block = dict(
        w1=sds(1, 1, wide, planes), s1=sds(planes), b1=sds(planes),
        w2=sds(3, 3, planes, planes), s2=sds(planes), b2=sds(planes),
        w3=sds(1, 1, planes, wide), s3=sds(wide), b3=sds(wide),
    )
    text = _compile(
        jax.grad(_bottleneck_stack, argnums=(0, 1)),
        sds(ROWS, size, size, wide), [block, block],
    )
    assert text.count("tpu_custom_call") == 2
    neighbours = _kernel_neighbours(text)
    activation = ROWS * size * size * planes  # the narrow side's elements
    assert any(elements >= activation for _, elements in neighbours)
    moved = [(opcode, elements) for opcode, elements in neighbours
             if elements >= activation
             and opcode in ("copy", "transpose")]
    assert not moved, moved


def test_routed_encoder_gets_no_elementwise_pass_before_the_kernel(one_chip, monkeypatch):
    """Inside a real encoder (flax Bottlenecks, a stride-2 stage edge) the
    kernel's ``dy`` has to stay an output of the conv fusion that applies the
    block's ReLU mask. Without the optimization barrier in
    ``ops/pointwise_bwd._bwd`` XLA moves the kernel's reshape up through the
    mask's ``select`` and every site gets an elementwise pass of its own over
    the wide tensors (measured on the chip, PERF.md section 6, PR 26: the
    step 20% slower than XLA's own). A two-block stack does not show it."""
    from simclr_pytorch_distributed_tpu.models import resnet

    monkeypatch.setattr(resnet, "_interpret_pallas", lambda: False)
    model = resnet.ResNet(
        block_cls=resnet.Bottleneck, stage_sizes=(2, 1, 1, 1), pointwise_bwd=True
    )
    variables = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((2, SIZE, SIZE, 3)), train=True)),
    )

    def loss(params, batch_stats, x):
        y, _ = model.apply({"params": params, "batch_stats": batch_stats}, x,
                           train=True, mutable=["batch_stats"])
        return jnp.mean(jnp.square(y))

    text = jax.jit(jax.grad(loss)).lower(
        variables["params"], variables["batch_stats"],
        jax.ShapeDtypeStruct((ROWS, SIZE, SIZE, 3), jnp.float32, sharding=one_chip),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 4  # not stage 4: over the VMEM budget
    wide = ROWS * SIZE * SIZE * 64  # a stage's narrow activation, in elements
    passes = [
        m.group(1)
        for m in map(_HLO_LINE.match, text[text.index("\nENTRY"):].splitlines())
        if m and "kind=kLoop" in m.group(4) and "transpose(jvp" in m.group(4)
        and _elements(m.group(2)) > wide
    ]
    assert not passes, passes


# ---- sparse attention's kernel pair (ops/sparse_attention.py) at the
# geometry of the cell keye-vl2-a3b-ep8.pretrain-1024px-b4: a 2-row group of
# 4,096 tokens, 32 query and 4 key-value heads of 128


def _attention_kernel_shapes(sharding, tokens, rows=2, heads=32, groups=4, d=128):
    """``(forward's, backward's)`` operands as ``sparse_attention._attend_fwd``
    and ``_attend_bwd`` hand them over."""

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    q_t, k, k_t = sds((rows, heads * d, tokens)), sds((rows, tokens, groups * d)), \
        sds((rows, groups * d, tokens))
    mask_t = sds((rows, tokens, tokens), jnp.int8)
    stat = sds((rows, groups, heads // groups, tokens), jnp.float32)
    return ((q_t, k, k_t, mask_t),
            (q_t, k, k_t, k, mask_t, stat, stat, stat, sds(q_t.shape, jnp.float32)))


_ATTENTION_CALLS = {
    "fwd": lambda *a: sparse_attention._forward_call(*a, n_heads=32, interpret=False),
    "bwd": lambda *a: sparse_attention._backward_call(*a, n_heads=32, interpret=False),
}


@pytest.mark.parametrize("tokens", [4096, 5120, 1024, 256])
@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_sparse_attention_kernels_compile_where_the_predicate_admits(one_chip, which, tokens):
    """4,096 tokens are the cell's; 5,120 the longest row inside the budget,
    256 the shortest that tiles (one key chunk of 256)."""
    assert sparse_attention.unsupported(tokens, 32, 4, 128) is None
    shapes = _attention_kernel_shapes(one_chip, tokens)[which == "bwd"]
    _compile(_ATTENTION_CALLS[which], *shapes)


def test_sparse_attention_budget_is_the_compilers(one_chip):
    """6,144 tokens: the predicate counts 16.5 MiB a block and leaves the
    layer on XLA's path; Mosaic, asked all the same, counts 16.70M against
    its 16.00M and refuses. (At 5,632 the predicate's 15.1 MiB is over its
    budget of 14 and Mosaic's 15.3M is not: the margin.)"""
    assert "16.5 MiB of VMEM" in sparse_attention.unsupported(6144, 32, 4, 128)
    with pytest.raises(Exception, match=r"(?i)vmem.*16\.70M and limit 16\.00M"):
        _compile(_ATTENTION_CALLS["fwd"], *_attention_kernel_shapes(one_chip, 6144)[0])
    assert "15.1 MiB of VMEM" in sparse_attention.unsupported(5632, 32, 4, 128)
    _compile(_ATTENTION_CALLS["fwd"], *_attention_kernel_shapes(one_chip, 5632)[0])


def _instructions(text):
    """``(computation, line, its _HLO_LINE match)`` of every instruction of a
    compiled program's text."""
    computation = None
    for line in text.splitlines():
        header = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+) \(.*\{\s*$", line)
        if header:
            computation = header.group(1)
            continue
        m = _HLO_LINE.match(line)
        if m:
            yield computation, line, m


def _top_level_arrays(text, within=None):
    """``[(opcode, dtype, dims)]`` of every array in the result type of every
    instruction outside fused computations and reducers: what goes through
    HBM between the compiled program's kernels. ``within``: of the named
    computations alone."""
    fused = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    found = []
    for computation, _, m in _instructions(text):
        if computation in fused or (within is not None and computation not in within):
            continue
        for dtype, dims in re.findall(r"\b([a-z]+\d+|pred)\[([\d,]+)\]", m.group(2)):
            found.append((m.group(3), dtype, tuple(int(x) for x in dims.split(","))))
    return found


@pytest.fixture(scope="module")
def attention_layer_texts(one_chip):
    """The compiled gradient of one ``SparseAttention`` layer at the cell's
    widths over a 2-row group, on the kernel pair and on XLA's path."""
    spec = token_encoder.TOKEN_ENCODERS["keye-vl2-a3b-ep8"]
    interpret = attention_layer._interpret_kernel
    attention_layer._interpret_kernel = lambda: False  # the host's backend is the CPU
    texts = {}
    try:
        for kernel in (True, False):
            layer = attention_layer.SparseAttention(
                **token_encoder.attention_attrs(spec, jnp.float32, kernel))
            assert layer.kernel_reason(4096) is None
            params = jax.tree.map(
                lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=one_chip),
                jax.eval_shape(lambda: layer.init(
                    jax.random.key(0), jnp.zeros((2, 16, spec.hidden))))["params"])

            def loss(params, h):
                out, kl = layer.apply({"params": params}, h)
                return jnp.sum(jnp.square(out)) + kl

            texts[kernel] = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
                params, jax.ShapeDtypeStruct((2, 4096, spec.hidden), jnp.float32,
                                             sharding=one_chip)).compile().as_text()
    finally:
        attention_layer._interpret_kernel = interpret
    return texts


def _score_blocks(text, spec):
    """Arrays that hold a chunk of queries, a group's heads and at least
    ``heads x q_chunk x q_chunk`` elements: a ``[groups, heads a group,
    q_chunk, keys]`` block of logits or probabilities in any order. (The
    indexer's ``[index heads, q_chunk, keys]`` products stay on both paths;
    the head-averaged target has no heads axis.)"""
    least = spec.n_heads * spec.q_chunk * spec.q_chunk
    return [(opcode, dtype, dims) for opcode, dtype, dims in _top_level_arrays(text)
            if spec.q_chunk in dims and spec.n_heads // spec.n_kv_heads in dims
            and math.prod(dims) >= least]


def test_no_score_block_leaves_the_kernels(attention_layer_texts):
    """XLA's path writes every ``[4, 8, 512, keys]`` block to HBM (this check
    sees them there); the layer on the kernel pair holds none, forward,
    recomputed or backward."""
    spec = token_encoder.TOKEN_ENCODERS["keye-vl2-a3b-ep8"]
    assert attention_layer_texts[True].count("tpu_custom_call") == 3
    assert "tpu_custom_call" not in attention_layer_texts[False]
    assert len(_score_blocks(attention_layer_texts[False], spec)) >= 3 * 8
    assert _score_blocks(attention_layer_texts[True], spec) == []


def test_no_layout_copy_around_the_attention_kernels(attention_layer_texts):
    """The kernels take queries minor (``[R, H*d, T]``), which is the order
    XLA's layout assignment gives ``q``, ``o`` and their cotangents, so the
    layer's transposes around the calls are bitcasts: no copy or transpose
    of a ``q``-sized tensor (2 x 4,096 x 4,096 elements) at all, where XLA's
    own path has one. With ``q`` as ``[R, T, H*d]`` there were seven a row
    group (PERF.md section 6, PR 29)."""
    q_sized = 2 * 4096 * 32 * 128
    moved = {kernel: [(opcode, dtype, dims) for opcode, dtype, dims in _top_level_arrays(text)
                      if opcode in ("copy", "transpose") and math.prod(dims) >= q_sized]
             for kernel, text in attention_layer_texts.items()}
    assert not moved[True], moved[True]
    assert len(moved[False]) <= 1, moved[False]


# ---- the expert layer's sweep (models/experts.py): what a trip moves that
# does not depend on its rows, and what the trip's size costs in memory


def _compile_expert_layer(one_chip, name, product_dtype):
    """The compiled gradient of one ``ExpertLayer`` at ``name``'s widths over
    a step's 8 rows of 4,096 tokens, its grouped products on operands of
    ``product_dtype`` (None: the layer's own float32)."""
    spec = token_encoder.TOKEN_ENCODERS[name]
    layer = experts.ExpertLayer(**token_encoder.expert_attrs(spec, jnp.float32, product_dtype))
    variables = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=one_chip),
        jax.eval_shape(lambda: layer.init(jax.random.key(0), jnp.zeros((2, 16, spec.hidden)))))
    params = variables.pop("params")

    def loss(params, h, rest):
        out, stats = layer.apply({**rest, "params": params}, h)
        return jnp.sum(jnp.square(out)) + stats["balance"]

    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, jax.ShapeDtypeStruct((8, 4096, spec.hidden), jnp.float32, sharding=one_chip),
        variables).compile()


@pytest.fixture(scope="module", params=[
    ("keye-vl2-a3b-ep8", "f32"), ("keye-vl2-a3b-ep8", "bf16"), ("moonlight-16b-a3b-ep8", "bf16")],
    ids="-".join)
def expert_layer_compiled(one_chip, request):
    """``(preset, the grouped products' operands by HLO's name, the compiled
    gradient)``: Keye's layer (16 x 2,048 x 768, softmax-routed) on float32
    operands, as off the TPU, and on bfloat16, as ``train.supcon.build`` sets
    them on one; Moonlight's (8 x 2,048 x 1,408, sigmoid-routed under its
    bias, shared experts beside) on bfloat16."""
    name, operands = request.param
    return (token_encoder.TOKEN_ENCODERS[name], operands, _compile_expert_layer(
        one_chip, name, {"f32": None, "bf16": jnp.bfloat16}[operands]))


def _loop_bodies(text):
    bodies = set(re.findall(r"body=%([\w.\-]+)", text))
    assert len(bodies) == 2  # the forward sweep and the backward one
    return bodies


def test_no_weight_is_laid_out_again_inside_the_expert_loops(expert_layer_compiled):
    """The backward sweep's input-gradient products take the weights with
    their last axes swapped; the swap is made once before the loop, and so is
    the weights' rounding to the products' type (on its bfloat16 copy the swap
    moves half the bytes). With ``jax.vjp`` of a chunk there were three
    ``copy`` instructions of a ``[16, 2048, 768]`` float32 tensor in the
    backward loop's body, 0.6 GB a trip (ISSUE 31). What is left there of
    that size, in either type, and moves bytes in the trip's own time: the
    three float32 weight gradients out of their grouped products and their
    three adds onto the carry. (A body's ``copy-start`` and ``slice-start`` of
    a weight are the compiler's prefetches into its nearer memory, same
    layout, asynchronous: PERF.md section 6, PR 31.)"""
    spec, _, compiled = expert_layer_compiled
    text = compiled.as_text()
    count = spec.held[1]
    inside = [(opcode, dtype) for opcode, dtype, dims in _top_level_arrays(
                  text, within=_loop_bodies(text))
              if dtype in ("f32", "bf16") and dims[0] == count
              and math.prod(dims) >= count * spec.hidden * spec.expert_width]
    moved = [found for found in inside
             if found[0] in ("copy", "transpose", "convert", "fusion")]
    assert moved == [], moved
    assert [dtype for opcode, dtype in inside if opcode == "add"] == ["f32"] * 3, inside


def test_grouped_products_read_the_operands_they_were_given(expert_layer_compiled):
    """The twelve ``ragged-dot`` Mosaic calls of the two loop bodies (three
    forward; nine backward: the chunk recomputed, three input gradients,
    three weight gradients) read rows and weights of the products' type, two
    bytes an element where ``build`` gives bfloat16, and give float32. The
    rows' rounding is the last instruction of the elementwise fusion that
    makes them: no fusion of a body only converts a ``[rows, 2048]`` or
    ``[rows, width]`` tensor."""
    spec, operands, compiled = expert_layer_compiled
    text = compiled.as_text()
    bodies = _loop_bodies(text)
    rows = {768: 32768, 1408: 24576}[spec.expert_width]  # a trip's, as the test below holds
    row_sized = rf"\b(bf16|f32)\[{rows},(?:{spec.hidden}|{spec.expert_width})\]"
    opcodes, calls, made = {}, [], set()
    for computation, _, m in _instructions(text):
        opcodes.setdefault(computation, set()).add(m.group(3))
    for computation, line, m in _instructions(text):
        if computation not in bodies:
            continue
        if m.group(1).startswith("ragged-dot-none"):
            constraints = re.search(r"operand_layout_constraints=\{(.*?)\}\}", line).group(1)
            calls.append((re.match(r"\(?(\w+)\[", m.group(2)).group(1),
                          tuple(re.findall(r"\b(f32|bf16)\[", constraints))))
        elif m.group(3) in ("fusion", "convert") and re.search(row_sized, m.group(2)):
            called = re.search(r"calls=%([\w.\-]+)", line)
            assert called and opcodes[called.group(1)] - {
                "parameter", "convert", "bitcast", "tuple"}, line[:200]
            made.update(re.findall(row_sized, m.group(2)))
    assert len(calls) == 12 and set(calls) == {("f32", (operands, operands))}, calls
    assert made == {"f32", operands}, made  # the rounded rows come out of such fusions


def test_a_trip_of_the_expert_sweep_holds_what_its_budget_says(expert_layer_compiled):
    """At the Keye cell's shapes ``experts.TRIP_BYTES`` gives 32,768 rows a
    trip (24,576 at the Moonlight cell's), asked with the layer's float32
    whatever the products read, and the rule counts 2.01 GB for such a trip:
    nine weight-sized tensors, 0.91 GB (three of them the gradients' sums,
    which are the layer's results and no temporaries), and 1.11 GB of rows.
    The chip's compiler holds the whole layer's gradient in 2.544 GB of
    temporaries there: the trip's 1.71 GB and, beside it, the layer's own
    input, result and cotangents (1.722 GB at the 8,192 rows of before, 3.651
    GB with the provision in one trip; PR 31). On bfloat16 operands it is
    2.578 GB: the rows are smaller, and the weights' three bfloat16 copies
    are held from the forward sweep to the backward one (the whole step,
    where a layer's backward recomputes its forward, holds 8.66 GB of
    temporaries for 8.91; PERF.md section 6, PR 33). Moonlight's layer, the
    shared experts' gradient among it: 3.000 GB on bfloat16 operands for
    3.252 on float32."""
    spec, operands, compiled = expert_layer_compiled
    assignments = 8 * 4096 * spec.top_k
    provisioned = experts.provisioned_rows(assignments, spec.held[1], spec.n_experts,
                                           spec.capacity_factor)
    rows = experts.balanced_chunk_rows(assignments, spec.held[1], spec.n_experts, provisioned,
                                       spec.hidden, spec.expert_width, jnp.float32)
    assert (rows, provisioned) == {768: (32768, 65536), 1408: (24576, 49152)}[spec.expert_width]
    weight = spec.held[1] * spec.hidden * spec.expert_width * 4
    trip = 6 * weight + 3 * (spec.hidden + spec.expert_width) * 4 * rows
    assert trip + 3 * weight <= experts.TRIP_BYTES
    temp = compiled.memory_analysis().temp_size_in_bytes
    most = {768: 2.544e9, 1408: 3.000e9}[spec.expert_width]
    assert trip * (1.0 if operands == "f32" else 0.7) < temp < 1.02 * most, temp


# ---- the whole step of the cell moonlight-16b-a3b-ep8.pretrain-1024px-b4
# (latent attention, a dense leading layer, shared and routed experts): 8 rows
# of 4,096 tokens through train.supcon's own program


def _cells_step(topo, tmp_path, name):
    """``ring_update`` of the cell of configuration ``name`` as
    ``benchmark/run.py`` builds it (the configuration's flags, global batch
    4, the resident store's 64-step epoch buffer), compiled from shapes for
    one described v5e."""
    import json

    from simclr_pytorch_distributed_tpu import config as config_lib
    from simclr_pytorch_distributed_tpu import recipes as recipes_lib
    from simclr_pytorch_distributed_tpu.ops.metrics import MetricRing
    from simclr_pytorch_distributed_tpu.parallel.mesh import (
        create_mesh, replicated_sharding, state_sharding)
    from simclr_pytorch_distributed_tpu.train import supcon
    from simclr_pytorch_distributed_tpu.train.supcon_step import metric_keys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", f"{name}.json")) as f:
        flags = json.load(f)["flags"]
    cfg = config_lib.parse_supcon(flags + [
        "--batch_size", "4", "--dataset", "synthetic", "--workdir", str(tmp_path)])
    mesh = create_mesh(devices=[topo.devices[0]])
    built = {}

    def abstract_state():
        model, schedule, tx, state, step_cfg = supcon.build(cfg, 64, 1)
        built.update(model=model, schedule=schedule, tx=tx, step_cfg=step_cfg)
        return state

    state = jax.eval_shape(abstract_state)
    state, recipe = recipes_lib.attach_for_config(cfg, built["model"], state,
                                                  schedule=built["schedule"])
    ring = MetricRing(cfg.print_freq, metric_keys(
        health=built["step_cfg"].health, online_probe=built["step_cfg"].online_probe,
        extra=recipe.metric_keys))
    update = supcon.make_fused_update(
        built["model"], built["tx"], built["schedule"], built["step_cfg"],
        supcon.make_augment_config(cfg), mesh, state, metric_ring=ring, resident=True,
        recipe=recipe)
    repl = replicated_sharding(mesh)
    placed = lambda x, s=repl: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)  # noqa: E731
    return update.lower(
        jax.tree.map(placed, state, state_sharding(mesh, state)),
        jax.tree.map(placed, jax.eval_shape(ring.init_buffer)),
        placed(jax.ShapeDtypeStruct((64, 4, 1024, 1024, 3), jnp.uint8)),
        placed(jax.ShapeDtypeStruct((64, 4), jnp.int32)),
        jax.tree.map(placed, jax.eval_shape(lambda: jax.random.key(0)))).compile()


def test_latent_cells_step_compiles_with_group_sized_dense_intermediates(topo, tmp_path):
    """The chip's compiler holds the step in 13.2 GB of arguments and
    temporaries (Keye's step, which loads, in 13.6); the dense layer's
    ``[tokens, 11264]`` intermediates are a 2-row group's (369 MB), never the
    batch's (1.48 GB); the expert sweep makes 2 trips of 24,576 rows over its
    provision."""
    name = "moonlight-16b-a3b-ep8"
    spec = token_encoder.TOKEN_ENCODERS[name]
    assert experts.balanced_chunk_rows(8 * 4096 * spec.top_k, spec.held[1], spec.n_experts,
                                       49152, spec.hidden, spec.expert_width,
                                       jnp.float32) == 24576
    compiled = _cells_step(topo, tmp_path, name)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 13.7e9
    wide = [dims for _, dtype, dims in _top_level_arrays(compiled.as_text())
            if spec.dense_width in dims]
    assert wide and max(math.prod(dims) for dims in wide) <= 2 * 4096 * spec.dense_width, wide


# ---- the whole step of the cell qwen3-next-80b-a3b-ep32.pretrain-1024px-b4
# (three Gated DeltaNet layers and a gated full-attention layer, 16 of 512
# experts with a gated shared expert) as a TPU's program builds it


@pytest.fixture(scope="module")
def delta_cells_step(topo, tmp_path_factory):
    """The cell's step built as on the chip (``jax.default_backend()`` reads
    "tpu": the grouped products on bfloat16 operands, the fused loss's
    kernels, the chunked delta rule and the convolution on their kernel
    pairs), compiled once for the tests below."""
    from simclr_pytorch_distributed_tpu.train import supcon

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(supcon.jax, "default_backend", lambda: "tpu")
        return _cells_step(topo, tmp_path_factory.mktemp("delta"), "qwen3-next-80b-a3b-ep32")


def test_delta_cells_step_compiles_with_group_sized_scan_tensors(delta_cells_step):
    """The chip's compiler holds the step in under 12.8 GB of arguments and
    temporaries (Moonlight's, which loads, in 13.2); the rule is nine Mosaic
    calls (three layers' forward, recomputed forward and backward), and no
    ``[..., 64, 64]`` block of it is left at the top level of the program,
    where XLA's path had a 2-row group's (the 64 chunks of 32 heads of two
    rows); the expert sweep makes one trip of 20,480 rows."""
    name = "qwen3-next-80b-a3b-ep32"
    spec = token_encoder.TOKEN_ENCODERS[name]
    assignments = 8 * 4096 * spec.top_k
    provisioned = experts.provisioned_rows(assignments, 16, 512, spec.capacity_factor)
    assert provisioned == 20480 and experts.balanced_chunk_rows(
        assignments, 16, 512, provisioned, spec.hidden, spec.expert_width, jnp.float32) == 20480
    compiled = delta_cells_step
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12.8e9
    text = compiled.as_text()
    # every call under its layer's delta_scan scope, the backward's too
    calls = [m.group(1) for line in text.splitlines() if "custom-call(" in line
             for m in [re.search(r'op_name="[^"]*/delta_scan/(delta_rule_\w+)/pallas_call', line)]
             if m]
    assert sorted(calls) == ["delta_rule_bwd"] * 3 + ["delta_rule_fwd"] * 6, calls
    # (the health check's eigenvalues split a [128, 128] matrix in four
    # [64, 64] blocks: no chunk's, which carry the chunks' and heads' axes)
    blocks = [dims for _, dtype, dims in _top_level_arrays(text)
              if len(dims) >= 3 and dims[-2:] == (64, 64)]
    assert blocks == [], blocks


def _short_conv_neighbours(text):
    """``{call: ([(opcode, dims)] of its operands, [(opcode, dims)] of its
    users)}`` for every ``short_conv`` Mosaic call of a compiled program, in
    its own computation and seen through bitcasts, reshapes and tuple
    elements (which move no bytes)."""
    ops, users, calls = {}, {}, []
    for computation, line, m in _instructions(text):
        name, shape, opcode, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest.split("),", 1)[0])
        dims = [tuple(int(d) for d in found.split(",") if d)
                for found in re.findall(r"\[([\d,]*)\]", shape)]
        ops[computation, name] = (opcode, dims, operands)
        for operand in operands:
            users.setdefault((computation, operand), []).append(name)
        call = re.search(r'op_name="[^"]*/short_conv/short_conv_(fwd|bwd)/pallas_call"', line)
        if call and opcode == "custom-call":
            calls.append((computation, name, call.group(1)))

    def walk(computation, name, step):
        for other in step(name):
            if (computation, other) not in ops:
                continue
            opcode, dims, _ = ops[computation, other]
            if opcode in _THROUGH:
                yield from walk(computation, other, step)
            else:
                yield opcode, dims

    return {(name, kind): (
        list(walk(computation, name, lambda n, c=computation: ops[c, n][2])),
        list(walk(computation, name, lambda n, c=computation: users.get((c, n), []))))
        for computation, name, kind in calls}


def test_the_convolution_reads_and_writes_the_projection_where_it_lies(delta_cells_step):
    """The causal convolution and its ``silu`` are six ``short_conv_fwd``
    calls (three layers' forward and recomputed forward) and three
    ``short_conv_bwd`` calls, each under its layer's ``short_conv`` scope,
    the backward's too. Each reads ``x`` from the ``[2, 4096, 12288]``
    projection as the product that makes it left it, with no slice of its
    first 8,192 columns; ``dx`` goes straight into the products that take the
    projection's cotangent (the weight's and the input's gradients), which
    read it beside ``z``'s: no copy, slice, concatenation or pad of the
    projection or of its cotangent sits around a call."""
    around = _short_conv_neighbours(delta_cells_step.as_text())
    assert sorted(kind for _, kind in around) == ["bwd"] * 3 + ["fwd"] * 6, list(around)
    projection, part = (2, 4096, 12288), (2, 4096, 8192)
    for (name, kind), (operands, users) in around.items():
        assert ("fusion", [projection]) in operands, (name, operands)
        moved = [(opcode, dims) for opcode, dims in operands + users
                 if opcode in ("copy", "transpose", "slice", "concatenate", "pad")
                 and (projection in dims or part in dims)]
        assert moved == [], (name, moved)
# ---- the chunked delta rule's kernel pair (ops/delta_rule.py) at the
# geometry of the cell qwen3-next-80b-a3b-ep32.pretrain-1024px-b4: a 2-row
# group of 4,096 tokens, 16 key and 32 value heads of 128, chunks of 64


def _delta_rule_calls(sharding, chunk=64, d=128, rows=2, tokens=4096, key_heads=16,
                      value_heads=32):
    """``{"fwd", "fwd-states", "bwd"}``: each call with its operands' shapes,
    as ``delta_rule._rule_fwd`` and ``_rule_bwd`` hand them over."""

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    qk, v, g = sds(rows, tokens, key_heads * d), sds(rows, tokens, value_heads * d), \
        sds(rows, tokens, value_heads)
    states = sds(rows, tokens // chunk, value_heads, d, d)
    geometry = dict(n_key_heads=key_heads, chunk=chunk, operands=jnp.bfloat16, interpret=False)

    def forward(keep_states):
        return lambda *a: delta_rule._forward_call(*a, keep_states=keep_states, **geometry)

    return {"fwd": (forward(False), (qk, qk, v, g, g)),
            "fwd-states": (forward(True), (qk, qk, v, g, g)),
            "bwd": (lambda *a: delta_rule._backward_call(*a, **geometry),
                    (qk, qk, v, g, g, states, v))}


@pytest.mark.parametrize("which", ["fwd", "fwd-states", "bwd"])
def test_delta_rule_kernels_compile_at_the_cells_shapes(one_chip, which):
    assert delta_rule.unsupported(4096, 64, 16, 32, 128, 128) is None
    fn, shapes = _delta_rule_calls(one_chip)[which]
    text = _compile(fn, *shapes)
    assert f"delta_rule_{which[:3]}" in text


def test_delta_rule_budget_is_the_compilers(one_chip):
    """The predicate counts a backward step's blocks and the temporaries the
    compiler adds beside them. Chunks of 128 (a key head's stacked block 256
    rows): 13.6 MiB, admitted, and Mosaic needs 12.45M of its 16.00M.
    Chunks of 64 over heads of 256: 16.9 MiB, refused, though Mosaic,
    asked all the same, needs 15.94M (the margin). Chunks of 192: 20.5 MiB,
    refused, and Mosaic refuses too."""
    admitted = delta_rule.vmem_bytes(128, 16, 32, 128, 128)
    assert 13 << 20 < admitted <= 14 << 20
    assert delta_rule.unsupported(1024, 128, 16, 32, 128, 128) is None
    fn, shapes = _delta_rule_calls(one_chip, chunk=128, tokens=1024)["bwd"]
    _compile(fn, *shapes)
    assert "16.9 MiB of VMEM" in delta_rule.unsupported(1024, 64, 16, 32, 256, 256)
    fn, shapes = _delta_rule_calls(one_chip, d=256, tokens=1024)["bwd"]
    _compile(fn, *shapes)
    assert "20.5 MiB of VMEM" in delta_rule.unsupported(768, 192, 16, 32, 128, 128)
    fn, shapes = _delta_rule_calls(one_chip, chunk=192, tokens=768)["bwd"]
    with pytest.raises(Exception, match=r"(?i)vmem.*19\.\d\dM and limit 16\.00M"):
        _compile(fn, *shapes)


# ---- the causal convolution's kernel pair (ops/short_conv.py) at the
# geometry of the cell qwen3-next-80b-a3b-ep32.pretrain-1024px-b4: a 2-row
# group of 4,096 tokens, x in the first 8,192 of the projection's 12,288
# columns, 4 taps


def _short_conv_calls(sharding, tokens=4096):
    """``{"fwd", "bwd"}``: each call with its operands' shapes, as
    ``short_conv._conv_fwd`` and ``_conv_bwd`` hand them over."""

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    x, w, dy = sds(2, tokens, 12288), sds(4, 8192), sds(2, tokens, 8192)
    return {"fwd": (lambda *a: short_conv._forward_call(*a, interpret=False), (x, w)),
            "bwd": (lambda *a: short_conv._backward_call(*a, interpret=False), (x, w, dy))}


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_short_conv_kernels_compile_at_the_cells_shapes(one_chip, which):
    assert short_conv.unsupported(4096, 8192, 4, jnp.float32) is None
    fn, shapes = _short_conv_calls(one_chip)[which]
    assert f"short_conv_{which}" in _compile(fn, *shapes)


def test_short_conv_budget_is_the_compilers(one_chip, monkeypatch):
    """``vmem_bytes`` counts what Mosaic counts for a backward step, to the
    hundredth of a MiB that its refusal prints: blocks of 1,024 tokens need
    12.14 MiB, admitted, and compile; blocks of 2,048 need 24.14 MiB,
    refused, and Mosaic refuses them too, for that count."""
    monkeypatch.setattr(short_conv, "TOKEN_BLOCK", 1024)
    assert short_conv.unsupported(4096, 8192, 4, jnp.float32) is None
    assert f"{short_conv.vmem_bytes(8192, 4) / 2**20:.2f}" == "12.14"
    fn, shapes = _short_conv_calls(one_chip)["bwd"]
    _compile(fn, *shapes)
    monkeypatch.setattr(short_conv, "TOKEN_BLOCK", 2048)
    assert "24.1 MiB of VMEM" in short_conv.unsupported(4096, 8192, 4, jnp.float32)
    need = f"{short_conv.vmem_bytes(8192, 4) / 2**20:.2f}M"
    fn, shapes = _short_conv_calls(one_chip)["bwd"]
    with pytest.raises(Exception, match=rf"(?i)vmem.*{re.escape(need)} and limit 16\.00M"):
        _compile(fn, *shapes)


# ---- the whole step of the cell lfm2-8b-a1b-ep4.pretrain-1024px-b4 (four
# gated short convolutions and one attention layer without a gate, a dense
# leading layer, 8 of 32 sigmoid-routed experts) as a TPU's program builds it


def test_conv_cells_step_compiles_with_group_sized_conv_and_dense_tensors(topo, tmp_path):
    """The chip's compiler holds the step in under 12.8 GB of arguments and
    temporaries (12.64 when written; Moonlight's, which loads at 79% of the
    chip, in 13.2); the gated convolutions' ``[tokens, 3 x 2048]``
    projections and the dense layer's ``[tokens, 7168]`` intermediates are a
    2-row group's, never the batch's; the gated convolution is named in the
    forward and in the backward; the expert sweep makes 3 trips of 22,016
    rows over its 65,536-row provision."""
    from simclr_pytorch_distributed_tpu.train import supcon

    name = "lfm2-8b-a1b-ep4"
    spec = token_encoder.TOKEN_ENCODERS[name]
    assignments = 8 * 4096 * spec.top_k
    provisioned = experts.provisioned_rows(assignments, 8, 32, spec.capacity_factor)
    assert provisioned == 65536 and experts.balanced_chunk_rows(
        assignments, 8, 32, provisioned, spec.hidden, spec.expert_width, jnp.float32) == 22016
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(supcon.jax, "default_backend", lambda: "tpu")
        compiled = _cells_step(topo, tmp_path, name)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12.8e9
    text = compiled.as_text()
    arrays = _top_level_arrays(text)
    for width in (3 * spec.hidden, spec.dense_width):
        wide = [dims for _, dtype, dims in arrays if width in dims]
        assert wide and max(math.prod(dims) for dims in wide) <= 2 * 4096 * width, wide
    assert any(dims == (22016, spec.expert_width) for _, _, dims in arrays)
    for direction in (r"jvp\(SupConResNet\)", r"transpose\(jvp\(SupConResNet\)\)"):
        assert re.search(rf'op_name="jit\(ring_update\)/{direction}/encoder/block4/attn/'
                         r'conv_mixer/[^"]*gated_conv/', text), direction
