#!/usr/bin/env python3
"""Write ``limits/<workload>.json`` from readings taken on the chip.

    python3 chipbench/set_limits.py <workload> FILE [FILE ...]

Each FILE is the JSON list `calibrate.py` writes: per seed, the
program's, the control's and each planted fault's readings.  For each
compared number the lower reading is the largest any sound program run
gave; the upper reading is the smallest the control gave where that is
at least three times the lower one, and the smallest of each planted
fault that reads at least ten times the lower one (three times for a
state left unchanged).  The limit lies a third of the way from the
upper reading down to the lower one on a log scale
(``lower**(1/3) * upper**(2/3)``): room on both sides, and twice the
factor above the lower reading, since fresh seeds read higher than the
calibrated ones.  A lower reading of 0 counts as ``ROUNDOFF`` in that
formula, except for the counts in ``EXACT``, whose limit is 0.  A
number without an upper reading gets no limit; the file keeps its
readings under ``not_compared``.
"""
from __future__ import annotations

import json
import os
import sys
from collections import defaultdict

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
FAULTS = ("frozen", "half_batch", "decision", "clock")
EXACT = ("decisions_changed", "decision_mismatch")
# float64 round-off of a sum of a few hundred terms
ROUNDOFF = 1e-15


def read(files):
    """(program readings, control readings, fault readings, seeds)."""
    prog, ctl = defaultdict(list), defaultdict(list)
    faults = defaultdict(lambda: defaultdict(list))
    seeds = set()
    for path in files:
        with open(path) as f:
            rows = json.load(f)
        for row in rows:
            seeds.add(row["seed"])
            for k, v in row["program"].items():
                prog[k].append(v)
            for k, v in row["control"].items():
                ctl[k].append(v)
            for fault in FAULTS:
                for k, v in row.get(fault, {}).items():
                    if isinstance(v, float):
                        faults[fault][k].append(v)
    return prog, ctl, faults, len(seeds)


def derive(prog, ctl, faults):
    out = {}
    for name, vals in prog.items():
        lower = max(vals)
        uppers = {}
        if ctl.get(name) and min(ctl[name]) >= 3.0 * lower:
            uppers["control"] = min(ctl[name])
        for fault, readings in faults.items():
            factor = 3.0 if fault == "frozen" else 10.0
            if readings.get(name) and min(readings[name]) >= factor * lower:
                uppers[fault] = min(readings[name])
        entry = {"lower": lower, "program_runs": len(vals),
                 "control": min(ctl[name]) if ctl.get(name) else None}
        for fault, readings in faults.items():
            if readings.get(name):
                entry[fault] = min(readings[name])
        uppers = {k: v for k, v in uppers.items() if v > 0}
        if uppers:
            entry["upper"] = min(uppers.values())
            entry["upper_from"] = min(uppers, key=uppers.get)
            if name in EXACT and lower == 0:
                entry["limit"] = 0.0
            else:
                low = max(lower, ROUNDOFF)
                entry["limit"] = float(
                    f"{low ** (1 / 3) * entry['upper'] ** (2 / 3):.3g}")
        out[name] = entry
    return out


def main(argv):
    workload, files = argv[0], argv[1:]
    prog, ctl, faults, n_seeds = read(files)
    limits = derive(prog, ctl, faults)
    for k, v in limits.items():
        print(k, v)
    doc = {"about": ("Limits of the numbers `correct` compares, set by "
                     "chipbench/set_limits.py from readings on the chip: "
                     f"{n_seeds} seeds of sound runs."),
           "limits": {k: v for k, v in limits.items() if "limit" in v},
           "not_compared": {k: v for k, v in limits.items()
                            if "limit" not in v}}
    with open(os.path.join(BENCH_DIR, "limits", workload + ".json"), "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    main(sys.argv[1:])
