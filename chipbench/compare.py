"""The comparison that decides ``correct``.

The program's readings come from the first ``check.rounds`` rounds of
set-up, taken through the window's own executable and feed: each
client's loss in every round, the eval loss at each segment's end, and
the norm of each parameter leaf's change from the initial weights at
the rounds ``check.delta_at`` (the state is only reachable at segment
boundaries).  The reference of the configuration's family
(`cells.family`: ``reference/<family>.py``) replays those rounds from
the same seed: its own weights, its own copy of the data, the
program's gather plan (which samples each client draws) and its
decisions (b, cut).  Numbers compared:

- ``loss1_gap``: the largest relative gap of a client's loss in the
  first round, at the initial weights;
- ``loss_gap``: the largest gap of a client's loss in any checked round,
  over the larger of the reference's loss and the first round's median
  loss (once a client has fitted its two-class shard its loss nears
  zero, and a relative gap there only says how far two trajectories
  drifted, exponentially);
- ``eval_loss_gap``: the largest relative gap of an eval loss;
- ``change<r>_gap``: for the worst leaf, the gap between the program's
  and the reference's norms of the change after round r, over the
  larger of the reference's norm of that leaf and of the median leaf.
  Leaves whose first-round gradient in the reference is under a
  thousandth of the median leaf's are left out (they move by round-off
  alone).

The host control loop's output, over every boundary of the run, against
`reference/control.py`:

- ``clock_gap``: the largest relative gap of the simulated clock the
  program reported at a boundary, against the reference's walk of
  Eqs. 28-40 under the program's decisions;
- ``decisions_changed``: how many decisions differ from the first
  (the fleet and the priors do not change, so none should);
- ``decision_gap`` (HASFL): the share by which the worst decision's
  Theta (Eq. 43) exceeds that of the reference's own search;
  ``decision_mismatch`` (a fixed policy): how many clients' batch size
  or cut differ from the one the traffic file states.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

from chipbench import cells
from chipbench.reference import control as CTL


def reference_readings(cell: Dict, seed: int, program: Dict, *,
                       dtype: str = "float32",
                       precision: str = "highest") -> Dict:
    """Replay the checked rounds in the plain reference."""
    import jax.numpy as jnp

    cfg, traffic = cell["config"], cell["traffic"]
    chk = traffic["check"]
    n = traffic["fleet"]["n"]
    ref = cells.family(cfg).reference
    (xtr, ytr), (xte, yte) = ref.data(cfg, traffic, seed)
    init = ref.init_params(cfg, seed)
    if program.get("init") is not None:
        worst = max(float(np.max(np.abs(np.asarray(init[k]) - v)))
                    for k, v in program["init"].items())
        print(f"initial weights: largest gap to the program's {worst!r}",
              flush=True)
    tr = ref.Trainer(cfg, init, n, lr=traffic["lr"],
                     clip=traffic["clip_norm"],
                     agg_interval=traffic["agg_interval"],
                     dtype=getattr(jnp, dtype), precision=precision)
    images, labels = jnp.asarray(xtr), jnp.asarray(ytr)
    test_x, test_y = jnp.asarray(xte), jnp.asarray(yte)
    counts = np.asarray(program["b"], int)
    l_c = int(np.max(program["cuts"]))
    per_seg = traffic["eval_every"]
    out = {"losses": {}, "evals": {}, "deltas": {}, "grad1": {}}
    for r in range(1, int(chk["rounds"]) + 1):
        seg, row = divmod(r - 1, per_seg)
        idx = program["idx"][seg][row]
        losses, gsq = tr.round(images, labels, idx, counts, l_c)
        out["losses"][r] = losses
        if r == 1:
            out["grad1"] = {k: math.sqrt(v) for k, v in gsq.items()}
        if r in chk["delta_at"]:
            out["deltas"][r] = tr.delta_norms()
        if r % per_seg == 0:
            out["evals"][r] = tr.evaluate(test_x, test_y)[0]
    return out


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def numbers(prog: Dict, ref: Dict, delta_at) -> Dict[str, float]:
    """The compared numbers, program (or control) against reference."""
    out = {}
    first = np.asarray(ref["losses"][1], np.float64)
    p1 = np.asarray(prog["losses"][1], np.float64)
    out["loss1_gap"] = _worst(np.abs(p1 - first) / np.abs(first))
    scale = float(np.median(np.abs(first)))
    worst = 0.0
    for r, ref_l in ref["losses"].items():
        p = np.asarray(prog["losses"][r], np.float64)
        q = np.asarray(ref_l, np.float64)
        worst = max(worst, _worst(np.abs(p - q) / np.maximum(np.abs(q), scale)))
    out["loss_gap"] = worst
    out["eval_loss_gap"] = max(
        (_rel(prog["evals"][r], v) if math.isfinite(prog["evals"][r])
         else math.inf) for r, v in ref["evals"].items())
    g1 = ref["grad1"]
    g_med = float(np.median(list(g1.values())))
    moving = [k for k, v in g1.items() if v >= 1e-3 * g_med]
    for r in delta_at:
        d_ref = ref["deltas"][r]
        d_prog = prog["deltas"][r]
        med = float(np.median([d_ref[k] for k in moving]))
        worst = 0.0
        for k in moving:
            gap = abs(d_prog[k] - d_ref[k]) / max(d_ref[k], med, 1e-30)
            worst = max(worst, gap if math.isfinite(gap) else math.inf)
        out[f"change{r}_gap"] = worst
    return out


def _fixed(policy: str):
    """``fixed(b=16,cut=4)`` -> (16, 4); None for any other policy."""
    if not policy.startswith("fixed(") or not policy.endswith(")"):
        return None
    kv = dict(p.split("=") for p in policy[len("fixed("):-1].split(","))
    return int(kv["b"]), int(kv["cut"])


def host_numbers(cell: Dict, prog: Dict, *, dtype=np.float64) -> Dict:
    """The control loop's numbers; ``dtype`` is the precision of the
    reference clock put in the program's place (the control)."""
    traffic = cell["traffic"]
    ctl = traffic["controller"]
    prof = cells.family(cell["config"]).counts.profile(cell["config"],
                                                      traffic)
    conv = dict(ctl, lr=traffic["lr"], agg_interval=traffic["agg_interval"])
    fleet, decisions = prog["fleet"], prog["decisions"]
    clocks = prog["clocks"]
    walk = CTL.clock(prof, fleet, ctl, decisions, traffic["agg_interval"],
                     traffic["reconfigure_every"], clocks[-1][0])
    out = {"clock_gap": max(_rel(c, walk[t - 1]) for t, c in clocks)}
    if dtype != np.float64:
        low = CTL.clock(prof, fleet, ctl, decisions, traffic["agg_interval"],
                        traffic["reconfigure_every"], clocks[-1][0],
                        dtype=dtype)
        out["clock_gap"] = max(_rel(low[t - 1], walk[t - 1])
                               for t, _ in clocks)
        return out
    b0, c0 = decisions[0]
    out["decisions_changed"] = float(sum(
        1 for b, c in decisions[1:]
        if not (np.array_equal(b, b0) and np.array_equal(c, c0))))
    fixed = _fixed(traffic["policy"])
    if fixed is not None:
        out["decision_mismatch"] = float(max(
            np.sum((np.asarray(b) != fixed[0]) | (np.asarray(c) != fixed[1]))
            for b, c in decisions))
    else:
        _, _, best = CTL.solve(prof, fleet, ctl, conv)
        out["decision_gap"] = max(
            CTL.decision_gap(prof, fleet, ctl, conv, b, c, best)
            for b, c in decisions)
    return out


def _worst(gaps: np.ndarray) -> float:
    return float(np.max(gaps)) if np.all(np.isfinite(gaps)) else math.inf


def check(cell: Dict, seed: int, program: Dict):
    """Each compared number beside its limit, in the limits file's order,
    and the numbers read but not compared (no limit could be set)."""
    ref = reference_readings(cell, seed, program)
    got = numbers(program, ref, cell["traffic"]["check"]["delta_at"])
    got.update(host_numbers(cell, program))
    limits = cell["limits"]["limits"]
    checks = {name: {"value": got.get(name, math.inf),
                     "limit": limits[name]["limit"]} for name in limits}
    others = {k: v for k, v in got.items() if k not in limits}
    return checks, others
