#!/usr/bin/env python3
"""chipbench/calibrate.py — the readings that a cell's limits are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 12 --controls 3

In one process, for each seed: the program's first steps (no measured
window: a training cell's readings need none) against the plain reference;
and on the first ``--controls`` seeds each of the configuration's
``controls`` (the reference in a precision below, in the program's place; the
first listed is THE control, the one the tests hold to the limits) and each
planted fault (the
reference with part of the batch left out) against the same reference.
Prints, for every number compared, the lower reading (the largest the
program gives) and each upper one (the smallest a control or a fault
gives).  The benchmark's own runs never run this; PERF.md records what it
printed and the limits set from it (``limits/<cell>.json``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import run  # noqa: E402


def values(program, want):
    return run.load_module("", "check").readings(program, want)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--leaves", type=int, default=0,
                    help="show this many leaves' (program, reference) "
                         "gradient and change norms, farthest apart first")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = run.Cell(args.workload, args.rehearse)
    batch = cell.traffic["batch"]
    faults = {"half_batch": {"rows": batch // 2}}
    if cell.chips > 1:
        faults["no_exchange"] = {"rows": batch // cell.chips}
    rows = []
    os.makedirs(os.path.join(run.ROOT, "chiprun_out"), exist_ok=True)
    out = os.path.join(run.ROOT, "chiprun_out",
                       "calibrate_%s.json" % args.workload)
    for i in range(args.seeds):
        # seeds of both sizes: small ones and ones past 2**31
        seed = args.first_seed + i + (2 ** 31 if i % 2 else 0)
        trainer, pool, program = cell.first_steps(seed)
        del trainer
        gc.collect()
        want = cell.follow(seed, pool)
        row = {"seed": seed, "losses": program["losses"],
               "reference_losses": want["losses"]}
        row["program"], row["worst_leaves"] = values(program, want)
        if args.leaves:  # the look: the leaves whose norms lie farthest apart
            far = sorted(want["grad_norms"], key=lambda n: -abs(
                program["grad_norms"][n] - want["grad_norms"][n]))
            row["leaves"] = {n: [program["grad_norms"][n],
                                 want["grad_norms"][n],
                                 program["delta_norms"][n],
                                 want["delta_norms"][n]]
                             for n in far[:args.leaves]}
        if i < args.controls:
            for precision in cell.cfg["controls"]:
                row["control_" + precision], _ = values(
                    cell.follow(seed, pool, precision=precision), want)
            for name, how in faults.items():
                row[name], _ = values(cell.follow(seed, pool, **how), want)
        print(json.dumps(row), flush=True)
        rows.append(row)
        with open(out + "l", "a") as f:  # rows survive a cut-off run
            f.write(json.dumps(row) + "\n")
    summary = {}
    for number in rows[0]["program"]:
        s = {"lower_max_program": max(r["program"][number] for r in rows),
             "program_sorted": sorted(r["program"][number] for r in rows)}
        for other in ["control_" + c for c in cell.cfg["controls"]] \
                + sorted(faults):
            got = [r[other][number] for r in rows if other in r]
            if got:
                s["upper_min_" + other] = min(got)
        summary[number] = s
    print(json.dumps({"workload": args.workload, "summary": summary},
                     indent=1))
    with open(out, "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
