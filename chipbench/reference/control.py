"""Plain reference of the host control loop's output: the simulated clock
and the quality of a HASFL decision.

Both follow arXiv:2506.08426 from the configuration's per-unit costs
(``profile(cfg, traffic)`` of its family's counts, `cells.family`) and
the fleet the traffic file describes; nothing here imports the program.

- The clock (Eqs. 28-40): every round adds the split-training latency
  T_S (Eq. 38), and every ``agg_interval``-th round the aggregation
  latency T_A (Eq. 39).
- A decision ``(b, cuts)`` is judged by the BCD objective (Eq. 43),
  Theta = R(b, L_c) * (T_S + T_A / I), with R the Corollary-1 round
  count (Eq. 27) on the Assumption-2 priors.  Theta is infinite where
  Corollary 1 has no solution (eps at or below the variance and drift
  terms).

A profile holds, for a cut after unit j (1-based, as the decisions
count): ``rho`` the cumulative forward operations per sample, ``bwd``
the cumulative backward operations, ``psi``/``chi`` the activation (and
its gradient) leaving unit j, in bits, ``delta`` the bits of units
1..j's parameters, and ``params`` each unit's parameter count.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def _fleet(fleet: Sequence[Dict], key: str) -> np.ndarray:
    return np.asarray([d[key] for d in fleet], np.float64)


def round_times(prof: Dict, fleet: Sequence[Dict], server: Dict, b,
                cuts) -> tuple:
    """(T_S, T_A) of one round, Eqs. 28-39."""
    b = np.asarray(b, np.float64)
    j = np.asarray(cuts, int) - 1
    f = _fleet(fleet, "flops")
    rho, bwd, psi, chi, delta = (prof[k][j] for k in
                                 ("rho", "bwd", "psi", "chi", "delta"))
    up = np.max(b * rho / f + b * psi / _fleet(fleet, "up_bw"))
    down = np.max(b * chi / _fleet(fleet, "down_bw") + b * bwd / f)
    srv = (np.sum(b * (prof["rho"][-1] - rho))
           + np.sum(b * (prof["bwd"][-1] - bwd))) / server["server_flops"]
    t_split = float(up + srv + down)
    lam = len(fleet) * np.max(delta) - np.sum(delta)     # Eq. 35/37
    t_srv = lam / server["server_fed_bw"]
    t_agg = float(max(np.max(delta / _fleet(fleet, "fed_up_bw")), t_srv)
                  + max(np.max(delta / _fleet(fleet, "fed_down_bw")), t_srv))
    return t_split, t_agg


def clock(prof: Dict, fleet: Sequence[Dict], server: Dict,
          decisions: List[tuple], agg_interval: int,
          reconfigure_every: int, rounds: int,
          dtype=np.float64) -> List[float]:
    """The simulated clock after each of rounds 1..``rounds``, under the
    decision in force in each round (``decisions[k]`` from round
    ``k * reconfigure_every + 1`` on)."""
    out, t = [], dtype(0.0)
    for r in range(1, rounds + 1):
        b, cuts = decisions[(r - 1) // reconfigure_every]
        t_split, t_agg = round_times(prof, fleet, server, b, cuts)
        t = dtype(t + dtype(t_split))
        if r % agg_interval == 0:
            t = dtype(t + dtype(t_agg))
        out.append(float(t))
    return out


def theta(prof: Dict, fleet: Sequence[Dict], server: Dict, conv: Dict, b,
          cuts) -> float:
    """Eq. 43: Corollary-1 rounds times the per-round latency; infinite
    where no round count reaches ``epsilon``, or where a device's memory
    (constraint C4) does not hold the decision."""
    b = np.asarray(b, np.float64)
    j = np.asarray(cuts, int) - 1
    n, lr, interval = len(fleet), conv["lr"], conv["agg_interval"]
    w = prof["params"] / prof["params"].sum()
    g_cum = np.cumsum(conv["g_sq_total"] * w)
    variance = (conv["beta"] * lr * conv["sigma_sq_total"] * np.sum(1.0 / b)
                / n ** 2)
    drift = (4 * conv["beta"] ** 2 * lr ** 2 * interval ** 2
             * g_cum[int(np.max(cuts)) - 1]) if interval > 1 else 0.0
    a = conv["epsilon"] - variance - drift
    mem = (b * (np.cumsum(prof["psi"])[j] + np.cumsum(prof["chi"])[j])
           + prof["delta"][j] * (1 + conv["optimizer_state_mult"]))
    if a <= 0 or np.any(mem >= _fleet(fleet, "memory")):
        return float("inf")
    t_split, t_agg = round_times(prof, fleet, server, b, cuts)
    return 2 * conv["theta_gap"] / (lr * a) * (t_split + t_agg / interval)


def _descend(prof, fleet, server, conv, b, cuts, moves) -> tuple:
    """Take single-client moves while one lowers Theta."""
    n_units = len(prof["rho"])
    best = theta(prof, fleet, server, conv, b, cuts)
    improved = np.isfinite(best)
    while improved:
        improved = False
        for i in range(len(b)):
            for db, dc in moves:
                nb, nc = b.copy(), cuts.copy()
                nb[i] += db
                nc[i] += dc
                if not (1 <= nb[i] <= conv["max_batch"]
                        and 1 <= nc[i] <= n_units):
                    continue
                t = theta(prof, fleet, server, conv, nb, nc)
                if t < best:
                    b, cuts, best, improved = nb, nc, t, True
    return b, cuts, best


def solve(prof: Dict, fleet: Sequence[Dict], server: Dict,
          conv: Dict) -> tuple:
    """A decision by plain search on Theta: the best common batch size
    and common cut, then single-client steps of a batch size or a cut
    while one lowers Theta.  Returns ``(b, cuts, Theta)``; Theta is
    infinite when no decision tried is feasible."""
    n, n_units = len(fleet), len(prof["rho"])
    best = (None, None, float("inf"))
    for c in range(1, n_units + 1):
        for b0 in range(1, conv["max_batch"] + 1):
            b, cuts = np.full(n, b0), np.full(n, c)
            t = theta(prof, fleet, server, conv, b, cuts)
            if t < best[2]:
                best = (b, cuts, t)
    if best[0] is None:
        return best
    return _descend(prof, fleet, server, conv, best[0], best[1],
                    ((1, 0), (-1, 0), (0, 1), (0, -1)))


def decision_gap(prof: Dict, fleet: Sequence[Dict], server: Dict,
                 conv: Dict, b, cuts, ref_theta: float) -> float:
    """The share by which a decision's Theta exceeds ``ref_theta`` (the
    reference's own), 0 where it is lower; infinite for a decision
    Theta cannot price."""
    got = theta(prof, fleet, server, conv, b, cuts)
    if not np.isfinite(got):
        return float("inf")
    return max(0.0, got / ref_theta - 1.0)
