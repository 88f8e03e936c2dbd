"""The comparison that decides ``correct``: the program's first three steps
against the plain reference's, number by number, each under its own limit.

Both sides hand over the same things: the loss of each step (host floats) and
three dicts of arrays under the reference's names: ``grad``, the first
gradient; ``change``, the parameters' change over the three steps; ``stats``,
the change of BN's running statistics over the first step. ``leaf_table``
reduces each dict to three norms a leaf (the program's, the reference's, and
that of their difference), and ``compared`` reduces those to the numbers:

- ``loss1_gap``, ``loss_gap``: the relative gap of the first step's loss, and
  the largest of the three steps'.
- ``grad_<which>_gap``, ``change_<which>_gap``: the gap between the two sides'
  norms of a leaf, against the reference's norm of that leaf or of the median
  leaf, whichever is larger; ``<which>`` is ``median``, ``p90`` or ``worst``
  over the leaves. A step that leaves its state as it was reads 1 on
  ``change``. (The norm of the two sides' difference says nothing here: at
  initialisation it reads 0.5 to 1 on sound runs, PERF.md.)
- ``stats_<which>_diff``: the norm of the two sides' difference of a leaf,
  against the same. The statistics are forward quantities, so the two sides
  agree far closer than on a gradient, and the difference sees what a gap of
  norms averages away: rounding that moves a leaf's entries and not its
  length. ``<which>`` is also ``early``: the worst of the first ten statistics
  in the order of the forward pass (the reference gives it under
  ``stats_order``), the stem's and the next four BN layers'. There the two
  sides agree to float32's rounding where every product rounds as the
  configuration states, and a lower precision reads a hundred times that;
  deeper in the network a sound run's own rounding grows layer by layer and
  covers it.

A cell's file holds limits for some of these (PERF.md, "How correct is
decided", gives the readings that each was set from); the rest are printed
and held to none. A number that is not finite is over every limit.
"""

from __future__ import annotations

import math
import statistics

GROUPS = ("grad", "change", "stats")
EARLY = 10  # statistics: mean and variance of the stem's BN and of the next four


def leaf_table(program: dict, reference: dict) -> dict:
    """group -> leaf -> (program's norm, reference's norm, norm of their
    difference), as host floats; one small program on the device."""
    import jax
    import jax.numpy as jnp

    def norms(p, r):
        n = lambda x: jnp.linalg.norm(x.astype(jnp.float32).ravel())  # noqa: E731
        return {g: {k: (n(p[g][k]), n(r[g][k]), n(p[g][k] - r[g][k])) for k in r[g]}
                for g in GROUPS}

    for g in GROUPS:
        if set(program[g]) != set(reference[g]):
            raise ValueError(f"{g}: the two sides name different leaves: "
                             f"{sorted(set(program[g]) ^ set(reference[g]))}")
    pick = lambda side: {g: side[g] for g in GROUPS}  # noqa: E731
    table = jax.device_get(jax.jit(norms)(pick(program), pick(reference)))
    return {g: {k: tuple(float(v) for v in row) for k, row in leaves.items()}
            for g, leaves in table.items()}


def _relative(values, reference_norms):
    """Each leaf's value against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    floor = statistics.median(reference_norms.values())
    return {k: v / max(reference_norms[k], floor) if math.isfinite(v) else math.inf
            for k, v in values.items()}


def _reduced(group: str, kind: str, per_leaf: dict) -> dict:
    ordered = sorted(per_leaf.values())
    worst = max(per_leaf, key=per_leaf.get)
    return {f"{group}_median_{kind}": statistics.median(ordered),
            f"{group}_p90_{kind}": ordered[min(len(ordered) - 1, (9 * len(ordered)) // 10)],
            f"{group}_worst_{kind}": per_leaf[worst],
            f"{group}_worst_{kind}_leaf": worst}


def _scalar_gap(p: float, r: float) -> float:
    return abs(p - r) / abs(r) if math.isfinite(p) else math.inf


def compared(program: dict, reference: dict, table: dict = None) -> dict:
    """Every number, limited or not. ``table`` is ``leaf_table``'s, where the
    caller has it already."""
    table = table if table is not None else leaf_table(program, reference)
    loss_gaps = [_scalar_gap(p, r) for p, r in zip(program["losses"], reference["losses"])]
    out = {"loss1_gap": loss_gaps[0], "loss_gap": max(loss_gaps)}
    for g in GROUPS:
        ref = {k: r for k, (_, r, _) in table[g].items()}
        if g != "stats":
            out.update(_reduced(g, "gap", _relative(
                {k: abs(p - r) for k, (p, r, _) in table[g].items()}, ref)))
            continue
        diff = _relative({k: d for k, (_, _, d) in table[g].items()}, ref)
        out["stats_early_diff"] = max(diff[k] for k in reference["stats_order"][:EARLY])
        out.update(_reduced(g, "diff", diff))
    return out


def verdict(numbers: dict, limits: dict):
    """(correct, table): the table has, for every limited number, its value
    and its limit; ``correct`` needs every one at or under its limit."""
    table = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(row["value"]) and row["value"] <= row["limit"]
             for row in table.values())
    return ok, table
