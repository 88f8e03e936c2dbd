"""Hot-loop sync lint: the zero-sync contract as a static property.

The dispatch-only hot loop is the repo's core perf invariant (PR 4/5/6:
between flush boundaries the main thread only dispatches — one ring D2H
per window, one index/window upload per epoch/window, nothing else). Until
now it was proven dynamically, one configuration at a time, by the
mechanical transfer-count tests. This rule makes it a whole-tree static
property over two region kinds:

- **jitted step builders**: any local function passed directly to
  ``jax.jit``/``jit`` (or decorated with it). Host-sync constructs inside
  would either crash at trace time (``float`` on a tracer) or silently
  constant-fold — both review-time findings;
- **boundary loops**: the innermost ``for``/``while`` enclosing a call
  that reaches ``TelemetrySession.flush_boundary`` (directly or through a
  local helper like the drivers' ``submit_window``) — exactly the
  boundary-to-boundary driver loops the zero-sync contract covers;
- **Pallas kernel builders**: any local function handed to
  ``pl.pallas_call`` as the kernel — directly, or through a
  ``functools.partial(<kernel>, ...)`` (possibly via an intermediate
  assignment, the ops/pallas_loss.py / ops/sparse_attention.py shape). A host
  sync inside a kernel body would either fail the TPU lowering or
  silently constant-fold in interpret mode while the compiled path
  diverges — both review-time findings.

Forbidden inside: ``jax.device_get``, ``.block_until_ready()``,
``.item()``, ``np.asarray``/``np.array`` (a device->host materialization),
and ``float()``/``bool()`` on non-literals (``__float__``/``__bool__`` on
a jax array is a blocking D2H). A DESIGNED sync point is annotated in
source with ``# sync-ok: <reason>`` on (or directly above) the line — the
annotation is the flush-boundary registry; a bare marker without a reason
is itself a finding.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Set, Tuple

from simclr_pytorch_distributed_tpu.analysis import callgraph
from simclr_pytorch_distributed_tpu.analysis.core import (
    Finding,
    LintModule,
    call_name,
    dotted_prefix,
)

RULE_LOOP = "hot-loop-sync:boundary-loop"
RULE_JIT = "hot-loop-sync:jitted-fn"
RULE_KERNEL = "hot-loop-sync:pallas-kernel"
RULE_ANNOTATION = "hot-loop-sync:annotation-missing-reason"

_SYNC_METHODS = frozenset({"block_until_ready", "item"})
_SYNC_CALLS = frozenset({"device_get"})
_NUMPY_MODULES = frozenset({"np", "numpy", "onp"})
_NUMPY_SYNC_FNS = frozenset({"asarray", "array"})
_SYNC_BUILTINS = frozenset({"float", "bool"})


def _sync_construct(node: ast.AST) -> str:
    """Non-empty description when ``node`` is a sync-forcing call."""
    if not isinstance(node, ast.Call):
        return ""
    name = call_name(node)
    if name in _SYNC_CALLS:
        return f"{name}() is a blocking device->host transfer"
    if name in _SYNC_METHODS and isinstance(node.func, ast.Attribute):
        return f".{name}() forces a device sync"
    if name in _NUMPY_SYNC_FNS and dotted_prefix(node) in _NUMPY_MODULES:
        return (
            f"{dotted_prefix(node)}.{name}() materializes its argument on "
            "the host (blocking D2H for device arrays)"
        )
    if (
        name in _SYNC_BUILTINS
        and isinstance(node.func, ast.Name)
        and node.args
        and not isinstance(node.args[0], ast.Constant)
    ):
        return (
            f"{name}() on a non-literal: __{name}__ on a traced/device "
            "value is a blocking readback"
        )
    return ""


def _jitted_functions(mod: LintModule) -> Set[ast.AST]:
    """Function defs compiled by jit: passed as jit's first positional
    argument, or decorated with @jit/@jax.jit/@partial(jax.jit, ...)."""
    by_name = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)
    out: Set[ast.AST] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and call_name(node) == "jit" \
                and node.args and isinstance(node.args[0], ast.Name):
            for fn in by_name.get(node.args[0].id, ()):
                out.add(fn)
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            if call_name(dec) == "jit" or (
                isinstance(dec, (ast.Name, ast.Attribute))
                and (getattr(dec, "id", None) == "jit"
                     or getattr(dec, "attr", None) == "jit")
            ):
                out.add(node)
            elif isinstance(dec, ast.Call) and call_name(dec) == "partial" \
                    and any(
                        (getattr(a, "id", None) == "jit"
                         or getattr(a, "attr", None) == "jit")
                        for a in dec.args
                    ):
                out.add(node)
    return out


def _pallas_kernel_functions(mod: LintModule) -> Set[ast.AST]:
    """Function defs handed to ``pallas_call`` as the kernel: the first
    positional argument as a bare Name, an inline
    ``functools.partial(<def>, ...)``, or a Name bound earlier in the
    module to such a partial (the ops/pallas_loss.py builder shape)."""
    by_name: dict = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)
    # var name -> EVERY function it is bound to via functools.partial,
    # module-wide: builders routinely reuse one local name ('kernel ='),
    # and a linter must over-approximate — resolving only the last
    # binding would silently drop all but one kernel from coverage
    partial_of: dict = {}
    for node in ast.walk(mod.tree):
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Call)
            and call_name(node.value) == "partial"
            and node.value.args
            and isinstance(node.value.args[0], ast.Name)
        ):
            partial_of.setdefault(node.targets[0].id, set()).add(
                node.value.args[0].id
            )
    out: Set[ast.AST] = set()
    for node in ast.walk(mod.tree):
        if not (
            isinstance(node, ast.Call)
            and call_name(node) == "pallas_call"
            and node.args
        ):
            continue
        arg = node.args[0]
        names = []
        if isinstance(arg, ast.Name):
            names.append(arg.id)
            names.extend(partial_of.get(arg.id, ()))
        elif (
            isinstance(arg, ast.Call)
            and call_name(arg) == "partial"
            and arg.args
            and isinstance(arg.args[0], ast.Name)
        ):
            names.append(arg.args[0].id)
        for nm in names:
            out.update(by_name.get(nm, ()))
    return out


def _boundary_loops(mod: LintModule) -> Set[ast.AST]:
    """Innermost loops enclosing a flush-boundary call — direct, or via a
    LOCAL helper (a function defined inside the same enclosing function,
    the drivers' ``submit_window`` shape). Module-level functions that
    reach the boundary (``train_one_epoch``) are deliberately not loop
    markers: the loop that calls one is the per-EPOCH driver loop, whose
    once-per-epoch host syncs (validation, TB schedule eval) sit outside
    the boundary-to-boundary contract."""
    reachers = callgraph.flush_boundary_reachers(mod)
    local_defs: dict = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            local_defs.setdefault(node.name, []).append(node)
    loops: Set[ast.AST] = set()
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        hits = name == "flush_boundary"
        if not hits and isinstance(node.func, ast.Name) and name in reachers:
            owner = mod.enclosing_function(node)
            hits = owner is not None and any(
                mod.enclosing_function(d) is owner
                for d in local_defs.get(name, ())
            )
        if not hits:
            continue
        cur = mod.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
                loops.add(cur)
                break
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                break  # the call runs in its own scope, not in this loop
            cur = mod.parent(cur)
    return loops


def _region_nodes(region: ast.AST) -> Iterator[ast.AST]:
    """Nodes executing in the region per iteration/trace: the subtree minus
    nested function bodies (a nested def runs on ITS call — the drivers'
    consume() callbacks run on the telemetry thread, where host syncs are
    the design)."""
    stack = list(ast.iter_child_nodes(region))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def check_module(mod: LintModule) -> List[Finding]:
    findings: List[Finding] = []
    regions: List[Tuple[str, str, ast.AST]] = []
    for fn in _jitted_functions(mod):
        regions.append((RULE_JIT, fn.name, fn))
    for fn in _pallas_kernel_functions(mod):
        regions.append((RULE_KERNEL, fn.name, fn))
    for loop in _boundary_loops(mod):
        owner = mod.enclosing_function(loop)
        owner_name = owner.name if owner is not None else "<module>"
        regions.append((RULE_LOOP, owner_name, loop))

    for rule, region_name, region in regions:
        for node in _region_nodes(region):
            desc = _sync_construct(node)
            if not desc:
                continue
            reason = mod.sync_ok_reason(node.lineno)
            sym = call_name(node)
            key = f"{rule}:{mod.rel}:{region_name}:{sym}"
            if reason:
                continue  # annotated flush-boundary site, reason recorded
            if reason is not None:  # marker present but empty
                findings.append(Finding(
                    rule=RULE_ANNOTATION, file=mod.rel, line=node.lineno,
                    why=(
                        "sync-ok annotation without a reason: every "
                        "designed sync point must record WHY it is outside "
                        "the zero-sync contract"
                    ),
                    allowlist_key=f"{RULE_ANNOTATION}:{mod.rel}:"
                                  f"{region_name}:{sym}",
                ))
                continue
            where = {
                RULE_JIT: "a jitted step function",
                RULE_KERNEL: "a Pallas kernel builder",
            }.get(rule, "a flush-boundary hot loop")
            findings.append(Finding(
                rule=rule, file=mod.rel, line=node.lineno,
                why=(
                    f"{desc} inside {where} ({region_name!r}): the "
                    "dispatch-only/zero-sync contract forbids host syncs "
                    "here — move it behind the flush boundary or annotate "
                    "a designed site with '# sync-ok: <reason>'"
                ),
                allowlist_key=key,
            ))
    return findings
