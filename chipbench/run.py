#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator it is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is
one JSON object: ``correct``, ``attempted`` (rounds in the window),
``failed`` (segments with a non-finite loss), ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with the reference, beside its limit (also the last
lines of standard error).  There is no CPU fallback: without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "jax")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"no program under {ROOT}/src: run from a checkout",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import cells

    cell = cells.load_cell(args.workload, ROOT)
    chips = cell["workload"]["chips"]
    # the compile cache lives at one fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    devs = jax.devices()
    print(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    if devs[0].platform != "tpu":
        print("no TPU found: this benchmark has no CPU fallback",
              file=sys.stderr)
        return 3
    if len(devs) < chips:
        print(f"the cell needs {chips} chips, found {len(devs)}",
              file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import harness

    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, devices=devs)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
