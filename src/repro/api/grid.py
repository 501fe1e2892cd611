"""The vmapped policy x scenario x seed grid runner (DESIGN.md §10, §13).

`run_group` executes a list of *compatible* sessions — same
`ExperimentSpec.grid_key()`: same model architecture and data shapes,
same `SFLConfig`, same round segmentation; policy, scenario preset,
seed, and partition are free axes — as one mega-run: every cell's
[N, ...]-stacked client units gain a leading grid axis, and each
training segment dispatches once as a jitted ``vmap`` of the scan
engine's donated-carry segment body instead of once per cell.

Seed crossing (DESIGN.md §13): cells built from different seeds carry
different data arrays, model inits, device pools, and host RNG streams.
All of that is already per-cell state — `Session` init runs per cell
before stacking (per-cell model/sampler init), gather plans and
participation plans are drawn from each cell's own sampler RNG, and
clocks walk each cell's own device pool — so the only shared-by-
construction piece was the device-resident dataset.  When the group's
seeds differ, the member stores' arrays are [G]-stacked
(`DeviceClientStore.stack_arrays`) and the vmapped body maps over them
with ``in_axes=0``; a same-seed group keeps the historical broadcast
(``in_axes=None``, one copy of the data on device).

Bitwise contract (tested in tests/test_api.py and gated by the
scenario-sweep ``--bench-grid`` mode): each cell's decision stream,
simulated clock, eval losses/accuracies, and final parameters are
bit-for-bit identical to running that cell alone through
`Session.run()`.  Three ingredients make this hold:

- per-slice vmap purity: the vmapped segment body reduces over exactly
  the same axes in the same order as the single-cell scan (verified
  empirically; XLA keeps per-slice reduction order when batching adds a
  leading axis);
- host-side parity: clocks, policy decisions, scenario traces, and the
  RNG index streams are advanced by the *same* per-cell host code the
  sequential scheduler uses (`SFLEdgeSimulator._advance_clock`,
  `DeviceClientStore.segment_indices`, the controller objects);
- bucket sub-grouping: a cell's gather plan is padded to its OWN
  ``pow2_bucket(b_max)`` — padding wider (e.g. to a grid-global
  maximum) regroups the batch-axis gradient reduction and is NOT
  bitwise-stable — so within a segment, cells whose current b_max falls
  in different buckets go out in separate vmapped dispatches (the grid
  is sliced, sub-stacked, and re-stitched; with one bucket the whole
  grid ships as a single donated carry and nothing is copied).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import split as SP
from repro.core.sfl import SimResult, pow2_bucket
from repro.utils.trace import span


def group_cells(specs) -> list:
    """Partition spec indices into grid-compatible groups, order-stable.

    Returns a list of index lists; specs with ``grid_key() is None``
    stay singletons and fall back to sequential `Session.run()` —
    non-scan engines, checkpointed cells, and traffic-enabled cells
    (the traffic plane's event walk rebinds store pools and rewrites
    parameter rows between scan dispatches: per-cell host state the
    vmapped mega-run cannot replay — the DESIGN.md §14 refuse-to-stack
    rule).
    """
    order, groups = [], {}
    for i, spec in enumerate(specs):
        key = spec.grid_key()
        if key is None:
            order.append([i])
            continue
        if key not in groups:
            groups[key] = []
            order.append(groups[key])
        groups[key].append(i)
    return order


def _stack_cells(states) -> list:
    """Per-cell unit lists ([N, ...] leaves) -> [G, N, ...]-stacked units."""
    n_units = len(states[0])
    return [
        jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[state[u] for state in states]
        )
        for u in range(n_units)
    ]


def _cell_state(grid, g: int) -> list:
    """Slice cell ``g``'s [N, ...] unit list out of the stacked grid."""
    return [jax.tree_util.tree_map(lambda a: a[g], u) for u in grid]


def run_group(sessions, *, verbose: bool = False) -> list:
    """Run grid-compatible sessions as one vmapped mega-run.

    The walk is the scan engine's segment scheduler
    (`SFLEdgeSimulator._run_scan`) lifted over a cell axis: one shared
    clock loop chops the round range at eval/reconfiguration
    boundaries, each segment dispatches per b_max bucket, and all
    per-cell host state (clocks, controllers, scenarios, RNG streams,
    metric records) advances through the cells' own simulator objects
    so single-spec semantics are preserved exactly.
    """
    sims = [s.sim for s in sessions]
    sim0 = sims[0]
    spec0 = sessions[0].spec
    n_cells = len(sessions)
    rounds = spec0.rounds
    eval_every = spec0.eval_every
    reconf = spec0.resolved_reconfigure_every
    n_units_total = len(sim0.units)

    # one executable per (segment length, b_pad, sub-group size); sim0's
    # bound segment body is shared by every cell (identical model arch +
    # SFL config is what grid_key guarantees — the *parameters* live in
    # the stacked carry, per cell).  Fault mode is part of grid_key, so
    # either every cell feeds a [R, N] participation plan (mapped over
    # the grid axis) or none does (soft: parts=None).  Data arrays only
    # depend on (seed, shape fields): a same-seed group broadcasts one
    # device-resident copy (in_axes=None, the historical layout), a
    # seed-crossing group maps over [G]-stacked per-cell arrays.
    faulty = spec0.fault_mode != "soft"
    uniform_data = len({s.spec.seed for s in sessions}) == 1
    grid_fn = jax.jit(
        jax.vmap(
            sim0._scan_segment,
            in_axes=(0, None, 0, 0, 0, None if uniform_data else 0,
                     0 if faulty else None),
        ),
        donate_argnums=(0,),
    )
    arrays_cache: dict = {}

    def arrays_for(members):
        """The dispatch's data operand for one member sub-group: the
        shared store on the same-seed path, the members' [G]-stacked
        per-cell stores otherwise (cached per sub-group — bucket
        partitions recur across segments)."""
        if uniform_data:
            return sim0.store.arrays
        key = tuple(members)
        if key not in arrays_cache:
            arrays_cache[key] = sim0.store.stack_arrays(
                [sims[g].store for g in members]
            )
        return arrays_cache[key]

    res = [SimResult() for _ in range(n_cells)]
    clocks = [0.0] * n_cells
    decisions = []
    for g, sess in enumerate(sessions):
        sims[g]._scenario_tick(sess.scenario, 0)
        b, cuts = sess.policy(sims[g], sims[g].rng)
        sims[g]._record_policy(res[g], b, cuts)
        decisions.append((np.asarray(b), np.asarray(cuts)))

    grid = _stack_cells([sim._stacked for sim in sims])

    def plans(members, t, nxt, b_pad):
        """Stack the member cells' per-segment gather plans/masks and
        (under a non-soft fault mode) participation plans."""
        seg = nxt - t
        idx, rmask, masks, parts = [], [], [], []
        for g in members:
            b, cuts = decisions[g]
            l_c_units = int(np.max(sims[g]._unit_cuts(cuts)))
            masks.append(
                SP.client_unit_mask(sim0.cfg, n_units_total, l_c_units)
            )
            idx.append(sims[g].store.segment_indices(seg, b, b_pad))
            rmask.append(sims[g].store.row_mask(b, b_pad))
            if faulty:
                parts.append(sims[g]._segment_participation(
                    t, nxt, b, cuts, sessions[g].scenario))
        return (
            jnp.asarray(np.stack(idx)),
            jnp.asarray(np.stack(rmask)),
            jnp.asarray(np.stack(masks)),
            jnp.stack(parts) if faulty else None,
        )

    def dispatch(carry, members, t, nxt, b_pad):
        """One vmapped segment dispatch for a bucket's member cells:
        plan, call and loss fetch, each in its span."""
        seg = nxt - t
        with span("plan", t=t):
            idx, rmask, masks, parts = plans(members, t, nxt, b_pad)
        rows = seg * sum(int(np.sum(decisions[g][0])) for g in members)
        with span("dispatch", t=t, rows=rows,
                  padded_rows=seg * len(members) * sim0.n * b_pad):
            carry, losses = grid_fn(
                carry, jnp.asarray(t, jnp.int32), idx, rmask, masks,
                arrays_for(members), parts
            )
        with span("fetch", t=t):
            losses = np.asarray(losses)
        return carry, losses

    t = 0
    while t < rounds:
        nxt = min(
            (t // eval_every + 1) * eval_every,
            (t // reconf + 1) * reconf,
            rounds,
        )
        t_seg = t
        with span("segment", t=t_seg, rounds=nxt - t_seg):
            buckets = {}
            for g, (b, _) in enumerate(decisions):
                buckets.setdefault(pow2_bucket(int(np.max(b))), []).append(g)

            seg_losses = [None] * n_cells
            if len(buckets) == 1:
                # uniform bucket: the whole grid is one donated carry
                b_pad, members = next(iter(buckets.items()))
                grid, losses = dispatch(grid, members, t, nxt, b_pad)
                for g in members:
                    seg_losses[g] = losses[g]
            else:
                cells = [_cell_state(grid, g) for g in range(n_cells)]
                new_cells = [None] * n_cells
                for b_pad, members in sorted(buckets.items()):
                    sub = _stack_cells([cells[g] for g in members])
                    sub, losses = dispatch(sub, members, t, nxt, b_pad)
                    for j, g in enumerate(members):
                        new_cells[g] = _cell_state(sub, j)
                        seg_losses[g] = losses[j]
                grid = _stack_cells(new_cells)

            with span("clock", t=t_seg):
                for g, sess in enumerate(sessions):
                    b, cuts = decisions[g]
                    clocks[g] = sims[g]._advance_clock(
                        clocks[g], t, nxt, b, cuts, sess.scenario
                    )
            t = nxt

            at_reconf = t % reconf == 0 and t < rounds
            at_eval = t % eval_every == 0 or t == rounds
            if at_reconf or at_eval:
                # controllers (online G²/σ² estimation) and eval both
                # read the live per-cell state through the cell's own
                # simulator
                for g in range(n_cells):
                    sims[g]._stacked = _cell_state(grid, g)
            if at_reconf:
                with span("control", t=t_seg):
                    for g, sess in enumerate(sessions):
                        b, cuts = sess.policy(sims[g], sims[g].rng)
                        sims[g]._record_policy(res[g], b, cuts)
                        decisions[g] = (np.asarray(b), np.asarray(cuts))
            if at_eval:
                for g in range(n_cells):
                    sims[g]._record_metrics(
                        res[g], t, clocks[g], seg_losses[g][-1], verbose,
                        seg_t=t_seg
                    )

    for g in range(n_cells):
        sims[g]._stacked = _cell_state(grid, g)
    return res
