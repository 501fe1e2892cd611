#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (run on the chip).

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--fault-seeds 3] [--out FILE] [--dump-trace DIR]

For each seed, in one process: the program's first ``check.rounds``
rounds through the cell's own entry, the reference replaying them at
float32 / ``highest``, the control (the reference at bfloat16, the
default precision, and its clock at float32) in the program's place,
and on the first
``--fault-seeds`` seeds the program with each fault of
`harness.FAULTS` the cell can have planted.  Prints and writes every
compared number: the program's are the lower readings, the control's
and the faults' the upper ones.  ``--dump-trace`` first makes one
traced run and writes its trace and a listing of its planes, lines and
op names there.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def dump_trace(trace_dir: str, out) -> None:
    """Planes, lines, and each distinct op name with its stats."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name}", file=out)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            seen = set()
            for e in events:
                key = e.name.split(".")[0]
                if key in seen or len(seen) >= 40:
                    continue
                seen.add(key)
                stats = {k: (v if isinstance(v, (int, float)) else str(v)[:160])
                         for k, v in e.stats}
                print(f"    {e.name!r} dur={e.duration_ns} {stats}", file=out)


def _raw(losses, rounds):
    """Each client's loss in each checked round, to 7 digits."""
    return [[float(f"{x:.7g}") for x in losses[r]] for r in rounds]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--dump-trace", default=None)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        BENCH_DIR, ".cache", "jax")
    import jax
    import numpy as np

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import cells, compare as CMP, harness

    cell = cells.load_cell(args.workload, ROOT)
    devs = jax.devices()
    if args.dump_trace:
        try:
            res = harness.run_cell(cell, 977, 2.0, True,
                                   t_start=time.perf_counter(), devices=devs,
                                   keep_trace=args.dump_trace)
            print(json.dumps(res), flush=True)
        except Exception:
            import traceback

            traceback.print_exc()
        if os.path.isdir(args.dump_trace):
            with open(os.path.join(args.dump_trace, "listing.txt"), "w") as f:
                dump_trace(args.dump_trace, f)
    chk = cell["traffic"]["check"]
    faults = harness.FAULTS
    rows = []
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        row = {"seed": seed}
        t = time.perf_counter()
        probe = harness.run_program(cell, seed, 0.0, False,
                                    setup_rounds=chk["rounds"])
        prog = harness.readings(probe)
        del probe
        gc.collect()
        row["program_s"] = time.perf_counter() - t
        t = time.perf_counter()
        ref = CMP.reference_readings(cell, seed, prog)
        row["reference_s"] = time.perf_counter() - t
        t = time.perf_counter()
        ctl = CMP.reference_readings(cell, seed, prog, dtype="bfloat16",
                                     precision="default")
        row["control_s"] = time.perf_counter() - t
        row["program"] = CMP.numbers(prog, ref, chk["delta_at"])
        row["program"].update(CMP.host_numbers(cell, prog))
        row["control"] = CMP.numbers(ctl, ref, chk["delta_at"])
        row["control"].update(CMP.host_numbers(cell, prog, dtype=np.float32))
        row["b"] = [int(x) for x in prog["b"]]
        row["cuts"] = sorted(set(int(x) for x in prog["cuts"]))
        rounds = sorted(ref["losses"])
        row["losses"] = {name: _raw(got["losses"], rounds) for name, got in
                         (("program", prog), ("reference", ref),
                          ("control", ctl))}
        del ctl
        if k < args.fault_seeds:
            for fault in faults:
                try:
                    probe = harness.run_program(cell, seed, 0.0, False, fault,
                                                setup_rounds=chk["rounds"])
                    got = harness.readings(probe)
                    del probe
                    gc.collect()
                    row[fault] = CMP.numbers(got, ref, chk["delta_at"])
                    row[fault].update(CMP.host_numbers(cell, got))
                    row["losses"][fault] = _raw(got["losses"], rounds)
                except Exception as e:  # a crashing fault reads as failed
                    row[fault] = {"error": repr(e)[:300]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
