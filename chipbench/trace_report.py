#!/usr/bin/env python3
"""One traced run of a cell, reduced by the program's spans and scopes.

    python3 chipbench/trace_report.py --workload <name> --seed <n> \\
        [--keep DIR] [--list N]

Runs the cell as ``chipbench/run.py --trace 1`` does (same set-up,
window, readers and check), keeps the profile in ``DIR`` (default
``chipbench/.out/kept/<workload>.<seed>``), and prints one JSON line:
the run's result, a digest of the checked rounds' losses (equal digests
mean bitwise-equal losses), the traced window's ms per round, and
`program_trace.report` of the window.  ``--list N`` first prints the
``repro.*`` spans of the window's first segment and the N op paths
with the most device time.
Needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "jax")


def loss_digest(losses) -> str:
    import numpy as np

    h = hashlib.sha256()
    for r in sorted(losses):
        h.update(np.asarray(losses[r], np.float32).tobytes())
    return h.hexdigest()[:16]


def listing(pt, n: int) -> None:
    """The ``repro.*`` spans of the window's first segment, and the ``n``
    op paths with the most device time in the window."""
    from chipbench import program_trace as PT

    first = min((s.arg("t") for s in PT.window_spans(pt)
                 if s.arg("t") is not None), default=None)
    for s in sorted(pt.spans, key=lambda s: s.start):
        if s.arg("t") == first:
            print(f"  {s.name} {1e3 * (s.end - s.start):.3f} ms {s.args}")
    lo, hi = pt.window
    by_path = defaultdict(float)
    for ops in pt.ops.values():
        for o in PT.work(ops):
            if o.start >= lo and o.end <= hi:
                by_path[o.path or "(no path)"] += o.end - o.start
    for p, s in sorted(by_path.items(), key=lambda kv: -kv[1])[:n]:
        print(f"  {s:.4f} s  {p}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--list", type=int, default=0)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import cells

    cell = cells.load_cell(args.workload, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import harness
    from chipbench import program_trace as PT

    keep = args.keep or os.path.join(
        BENCH_DIR, ".out", "kept", f"{args.workload}.{args.seed}")
    seen = {}
    readings = harness.readings

    def keep_readings(probe):
        seen["digest"] = loss_digest(probe.losses)
        seen["window"] = probe.window()
        return readings(probe)

    harness.readings = keep_readings
    out = harness.run_cell(cell, args.seed, 0.0, True, t_start=T_START,
                           devices=devs, keep_trace=keep)
    pt = PT.load(keep)
    if args.list:
        listing(pt, args.list)
    win = seen["window"]
    out["loss_digest"] = seen["digest"]
    out["traced_ms_per_round"] = 1e3 * win["wall_s"] / win["rounds"]
    out["program"] = PT.report(pt, cell["traffic"]["trace_rounds"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
