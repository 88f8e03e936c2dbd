#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, taken on the chip at
a cell's own size, in one process. Not run by the benchmark's own runs.

    python3 benchmark/control.py --workload <cell> --seeds 101,102,... \\
        [--bf16-seeds 201,202,203] [--fault-seeds 301,302,303] [--looks] \\
        --out chiprun_out/readings_<cell>.json
    python3 benchmark/control.py --workload <cell> --judge chiprun_out/readings_<cell>.json

Every row goes through the harness's own ``drive`` (set-up through
``train_one_epoch``, a window of no length, the readings, the reference, the
comparison under the cell's committed limits) and is written out with every
number of ``compare.compared`` and the three norms of every leaf:

- ``--seeds``: the program as the configuration states it (the lower readings);
- ``--bf16-seeds``: the control, the program with its own lower precision
  switched on (``--bf16``: bfloat16 activations under float32 statistics);
- ``--fault-seeds``: the program as stated once more and, put in its place,
  the reference with half of the batch left out;
- ``--looks``: for every row of the program, a look at where its distance
  from the reference comes from: the same readings against the reference with
  the crop's interpolation taken exactly in float32.

A state left unchanged reads 1 on ``change_median_gap`` by the measure's
definition and needs no run. ``--judge`` reads such a file back and holds
every row to the limits that the cell's file has now, through
``compare.verdict``: no chip needed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import compare  # noqa: E402
import run as harness  # noqa: E402


def seeds_of(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def judge(cell: dict, path: str) -> int:
    config = cell["config_file"]
    order = harness.load_module(config["reference"]).stats_order(config["model"])
    with open(path) as f:
        rows = json.load(f)
    for row in rows:
        side = lambda i: {"losses": row["losses"][i], "stats_order": order}  # noqa: E731
        ok, table = compare.verdict(compare.compared(side(0), side(1), row["leaves"]),
                                    cell["limits"])
        over = [f"{k} {v['value']:.3g} > {v['limit']:.3g}" for k, v in table.items()
                if not v["value"] <= v["limit"]]
        print(f"{row['kind']:<58} seed {row['seed']:<11} correct {ok!s:<5} {'; '.join(over)}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--bf16-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--looks", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--judge", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.judge:
        return judge(cell, args.judge)
    if not args.rehearse:
        harness.require_tpu(cell["chips"])
    rows = []

    def note(seed, kind, program, reference, table=None, **more):
        table = table or compare.leaf_table(program, reference)
        rows.append({"seed": seed, "kind": kind, **more,
                     **compare.compared(program, reference, table),
                     "losses": [program["losses"], reference["losses"]],
                     "leaves": table})
        print(json.dumps({k: v for k, v in rows[-1].items() if k != "leaves"}), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f)

    def one(seed, kind, overrides=()):
        keep = {}
        res = harness.drive(args.workload, seed, 0.0, False, args.rehearse,
                            flag_overrides=overrides, keep=keep)
        note(seed, kind, keep["program"], keep["reference"], keep["table"],
             correct=res["correct"])
        if args.looks:
            note(seed, kind + " | reference: crop's interpolation exact",
                 keep["program"], keep["follow"](resize_precision="highest"))
        return keep

    for seed in seeds_of(args.seeds):
        one(seed, "program")
    for seed in seeds_of(args.bf16_seeds):
        one(seed, "control: program --bf16", ["--bf16"])
    for seed in seeds_of(args.fault_seeds):
        keep = one(seed, "program")
        note(seed, "fault: half of the batch left out (reference)",
             keep["follow"](drop_half=True), keep["reference"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
