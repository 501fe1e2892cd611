#!/usr/bin/env python3
"""Smoke check: the edge simulator's main path on a TPU, at full width.

Drives `ExperimentSpec` -> `Session` -> the scan engine once on the
paper's own model, `vgg16-cifar` (15.2M parameters, every layer at its
published width), with the paper's default fleet of N=20 clients.  The
weights are random from the spec's seed and the data is synthetic.  One
process, phases in order:

1. device check: the first device must be a TPU (no CPU fallback);
2. main path: `hasfl` with online estimation, non-IID shards, two scan
   segments and one reconfiguration; every loss finite, the simulated
   clock increasing;
3. reference engine: a fixed-policy spec on the scan engine against the
   per-round vectorized engine;
4. kernel path: ``runner="auto"`` must leave a CNN spec's kernel knobs
   unset on a TPU (XLA's convolution and the inline update); the same
   spec with both knobs pinned to ``"kernel"`` — the native Pallas
   batched conv and fused clip+SGD — goes through
   ``Session.run_grid(runner="auto")`` with its pins kept, its
   executable must hold ``tpu_custom_call``, and it is checked against
   phase 3.

``--four-chips`` runs only the mesh path and its reference instead: the
phase-3 spec sharded over four devices against the same spec on one.

Usage::

    python chip_smoke.py               # phases 1-4, one chip
    python chip_smoke.py --four-chips  # phases 1 and 5, four chips

The last line of stdout is one JSON object naming the device; it says
``"ok": true`` only when every phase passed.  Timings printed on the way
are smoke timings, not benchmarks.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

ARCH = "vgg16-cifar"
N_CLIENTS = 20            # paper Table I default (SFLConfig.n_devices)
N_TRAIN = 5000            # 250 samples per client: feeds b <= max_batch=64
N_TEST = 500
ROUNDS = 6
SEGMENT = 3               # eval_every = reconfigure_every = agg_interval
FIXED_POLICY = "fixed(b=16,cut=4)"

# TPU default precision: an f32 matmul or convolution makes one bf16
# pass — each operand is rounded to bf16 (unit roundoff u = 2**-8), so a
# single product carries a relative error of up to 2u.  Paths that round
# at different places (the XLA conv vs the Pallas kernel, the per-round
# step vs the scan body, one device vs four) may differ by that much per
# product.  A loss averaged over clients and samples, or a parameter
# after a few clipped lr steps, is no further apart unless a path is
# wrong, so both comparisons use 2u: losses relative to themselves,
# parameters relative to the model's largest weight.  The default
# precision is left as it is.
BF16_U = 2.0 ** -8
LOSS_RTOL = 2 * BF16_U
PARAM_RTOL = 2 * BF16_U


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B ({peak / 2**30:.3f} GiB)"


def base_spec(**kw):
    from repro.api import ExperimentSpec
    from repro.config import SFLConfig

    fields = dict(
        arch=ARCH, n_clients=N_CLIENTS, partition="noniid-shards",
        n_train=N_TRAIN, n_test=N_TEST, policy="hasfl", estimate=True,
        rounds=ROUNDS, eval_every=SEGMENT, reconfigure_every=SEGMENT,
        sfl=SFLConfig(n_devices=N_CLIENTS, agg_interval=SEGMENT, lr=0.05),
    )
    fields.update(kw)
    return ExperimentSpec(**fields)


def fixed_spec(**kw):
    return base_spec(policy=FIXED_POLICY, estimate=False, **kw)


def check_result(res, label: str) -> None:
    losses = list(res.train_loss) + list(res.test_loss)
    check(len(res.train_loss) == ROUNDS // SEGMENT,
          f"{label}: {len(res.train_loss)} evals, expected {ROUNDS // SEGMENT}")
    check(all(math.isfinite(x) for x in losses),
          f"{label}: non-finite loss in {losses}")
    clock = [0.0] + list(res.clock)
    check(all(b > a for a, b in zip(clock, clock[1:])),
          f"{label}: simulated clock does not increase: {res.clock}")


def same_decisions(a, b, label: str) -> None:
    import numpy as np

    check(a.rounds == b.rounds, f"{label}: eval rounds differ")
    check(a.clock == b.clock, f"{label}: clocks differ: {a.clock} vs {b.clock}")
    for name in ("b_history", "cut_history"):
        ha, hb = getattr(a, name), getattr(b, name)
        check(len(ha) == len(hb)
              and all(np.array_equal(x, y) for x, y in zip(ha, hb)),
              f"{label}: {name} differs")


def close_losses(a, b, label: str) -> float:
    """Largest relative loss gap; fails beyond LOSS_RTOL."""
    worst = 0.0
    for x, y in zip(list(a.train_loss) + list(a.test_loss),
                    list(b.train_loss) + list(b.test_loss)):
        worst = max(worst, abs(x - y) / max(abs(y), 1e-30))
    check(worst <= LOSS_RTOL,
          f"{label}: relative loss gap {worst} > {LOSS_RTOL}")
    return worst


def timed_scan(sim, record: list):
    """Wrap the simulator's segment executable to time each dispatch
    (blocking on its result); the wrapped call itself is unchanged."""
    import jax

    inner = sim._scan_fn

    def scan(stacked, *rest):
        t = time.perf_counter()
        out = jax.block_until_ready(inner(stacked, *rest))
        record.append((time.perf_counter() - t, rest))
        return out

    sim._scan_fn = scan
    return inner


def phase_main(dev) -> None:
    import jax
    from repro.api import Session

    sess = Session(base_spec())
    calls: list = []
    inner = timed_scan(sess.sim, calls)
    res = sess.run()
    check_result(res, "main path")
    for i, (b, cut) in enumerate(zip(res.b_history, res.cut_history)):
        say(f"  segment {i + 1} (rounds {i * SEGMENT + 1}-{(i + 1) * SEGMENT}): "
            f"b={b.tolist()} cut={cut.tolist()}")
    say(f"  train_loss={res.train_loss} test_loss={res.test_loss} "
        f"test_acc={res.test_acc}")
    say(f"  simulated clock={res.clock}")
    # steady state: replay the last segment's shapes on the warm executable
    rest = calls[-1][1]
    t = time.perf_counter()
    jax.block_until_ready(inner(sess.sim._stacked, *rest))
    steady = time.perf_counter() - t
    first = calls[0][0]
    say(f"  smoke timing, not a benchmark: first segment {first:.3f} s "
        f"(compile + {SEGMENT} rounds; compile ~{first - steady:.3f} s), "
        f"steady {1e3 * steady / SEGMENT:.3f} ms/round")
    say(f"  peak_bytes_in_use: {peak_bytes(dev)}")


def phase_reference(dev):
    from repro.api import Session

    out = {}
    for engine in ("scan", "vectorized"):
        out[engine] = Session(fixed_spec(engine=engine)).run()
        check_result(out[engine], f"reference/{engine}")
    same_decisions(out["scan"], out["vectorized"], "scan vs vectorized")
    gap = close_losses(out["scan"], out["vectorized"], "scan vs vectorized")
    say(f"  scan test_loss={out['scan'].test_loss} "
        f"vectorized test_loss={out['vectorized'].test_loss}")
    say(f"  relative loss gap {gap:.3e} (limit {LOSS_RTOL:.3e})")
    say(f"  peak_bytes_in_use: {peak_bytes(dev)}")
    return out["scan"]


def kernel_executable_text(spec) -> tuple:
    """Lower the kernel-path scan segment exactly as `Session.run` will
    call it; returns (HLO text, memory analysis)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.api import Session
    from repro.core import split as SP
    from repro.core.sfl import pow2_bucket

    sim = Session(spec).sim
    b = np.full(sim.n, 16)
    cuts = np.full(sim.n, 4)
    b_pad = pow2_bucket(int(b.max()))
    l_c = int(np.max(sim._unit_cuts(cuts)))
    masks = jnp.asarray(SP.client_unit_mask(sim.cfg, len(sim.units), l_c))
    compiled = sim._scan_fn.lower(
        sim._stacked, jnp.asarray(0, jnp.int32),
        sim.store.segment_indices(SEGMENT, b, b_pad),
        sim.store.row_mask(b, b_pad), masks, sim.store.arrays, None,
    ).compile()
    return compiled.as_text(), compiled.memory_analysis()


def phase_kernel(dev, ref) -> None:
    from repro.api import Session
    from repro.api import runners as R

    auto = R.apply_choice(fixed_spec())
    say(f"  runner auto -> conv_impl={auto.conv_impl} "
        f"update_impl={auto.update_impl}")
    check(auto.conv_impl is None and auto.update_impl is None,
          "runner='auto' filled a kernel knob of a CNN spec on this backend")
    spec = fixed_spec(conv_impl="kernel", update_impl="kernel")
    check(R.apply_choice(spec) == spec, "runner='auto' changed a pinned spec")
    text, ma = kernel_executable_text(spec)
    check("tpu_custom_call" in text,
          "kernel-path scan executable holds no tpu_custom_call")
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    say(f"  scan executable: tpu_custom_call present; memory_analysis "
        f"{total} B ({total / 2**30:.3f} GiB) at b=16")
    (res,) = Session.run_grid([spec], runner="auto")
    check_result(res, "kernel path")
    same_decisions(res, ref, "kernel vs scan oracle")
    gap = close_losses(res, ref, "kernel vs scan oracle")
    say(f"  kernel test_loss={res.test_loss}")
    say(f"  relative loss gap to phase 3 {gap:.3e} (limit {LOSS_RTOL:.3e})")
    say(f"  peak_bytes_in_use: {peak_bytes(dev)}")


def phase_mesh(devs) -> None:
    import jax
    import numpy as np
    from repro.api import Session
    from repro.mesh import MeshSpec

    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    sessions = {}
    for d in (4, 1):
        sess = Session(fixed_spec(mesh=MeshSpec(n_edges=4, devices=d)))
        res = sess.run()
        check_result(res, f"mesh d={d}")
        sessions[d] = (sess, res)
    (s4, r4), (s1, r1) = sessions[4], sessions[1]
    same_decisions(r4, r1, "mesh d=4 vs d=1")
    gap = close_losses(r4, r1, "mesh d=4 vs d=1")
    leaves4 = jax.tree_util.tree_leaves(s4.sim._stacked)
    leaves1 = jax.tree_util.tree_leaves(s1.sim._stacked)
    scale = max(float(np.abs(np.asarray(x)).max()) for x in leaves1)
    worst = max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
                for x, y in zip(leaves4, leaves1))
    check(worst <= PARAM_RTOL * scale,
          f"mesh d=4 vs d=1: parameter gap {worst} > {PARAM_RTOL} x {scale}")
    for leaf in leaves4:
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == 4,
              f"carry leaf {leaf.shape} is not spread over 4 devices")
        check(all(s.data.shape[0] == leaf.shape[0] // 4 for s in shards),
              f"carry leaf {leaf.shape} shards are not 1/4 of the clients")
    say(f"  relative loss gap {gap:.3e} (limit {LOSS_RTOL:.3e}); parameter "
        f"gap {worst:.3e} (limit {PARAM_RTOL * scale:.3e})")
    say(f"  every carry leaf holds {N_CLIENTS // 4} of {N_CLIENTS} clients "
        f"per device")
    for dev in devs[:4]:
        say(f"  {dev}: peak_bytes_in_use {peak_bytes(dev)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path on four chips against "
                         "its one-chip reference")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        say(f"FAIL: no repro package under {SRC}; run from a checkout")
        return 2
    sys.path.insert(0, SRC)
    import jax

    devs = jax.devices()
    dev = devs[0]
    say(f"phase 1: device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        say("FAIL: no TPU found; this smoke has no CPU fallback")
        return 1

    from repro.utils.cache import enable_compilation_cache

    say(f"compilation cache: {enable_compilation_cache()}")
    if args.four_chips:
        phases = [("phase 5: mesh d=4 vs d=1", lambda: phase_mesh(devs))]
    else:
        ref = {}
        phases = [
            ("phase 2: main path", lambda: phase_main(dev)),
            ("phase 3: scan vs vectorized",
             lambda: ref.setdefault("scan", phase_reference(dev))),
            ("phase 4: kernel path", lambda: phase_kernel(dev, ref["scan"])),
        ]
    for name, fn in phases:
        say(f"{name} ({ARCH}, N={N_CLIENTS})")
        t = time.perf_counter()
        try:
            fn()
        except SmokeFailure as e:
            say(f"FAIL: {e}")
            return 1
        say(f"  {name.split(':')[0]} passed in {time.perf_counter() - t:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
