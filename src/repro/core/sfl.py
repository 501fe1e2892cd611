"""The SFL/HASFL training runtime.

Two execution paths share the same algorithmic semantics (Algorithm 1):

1. **SFLEdgeSimulator** — the paper-faithful edge-computing simulation:
   N heterogeneous clients, per-client batch b_i and cut c_i, server-common
   sub-model aggregated every round (Eq. 4), client-specific sub-models
   (client-side + server-non-common) aggregated every I rounds (Eq. 7),
   wall-clock advanced by the Eqns (28)-(40) latency model, metrics on a
   held-out set. Used by all paper-figure benchmarks.  Three round
   engines (``legacy`` / ``vectorized`` / ``scan``) share one update rule
   (`split.hasfl_round_update`); the scan engine runs whole segments of
   rounds device-resident (DESIGN.md §8).

2. **make_hasfl_train_step** — the SPMD pod realization: client-stacked
   prefix parameters [N, ...] sharded over the data axis, server suffix
   2-D sharded, delayed every-I aggregation executed inside the jitted
   step (a `jnp.where` on step % I).  This is what the multi-pod dry-run
   lowers for the `train_4k` shape.

Key correctness note (DESIGN.md §2): within a round, split execution
computes exactly the same gradients as full-model execution — the *only*
algorithmic deviations of SFL from centralized SGD are the aggregation
schedules.  The simulator therefore computes per-client full-model
gradients and applies HASFL's per-component update rules, which is
mathematically identical to shipping activations (and is what makes the
simulation exact rather than approximate).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import SFLConfig, DeviceProfile, CNN
from repro.core.latency import LatencyModel
from repro.core.profiles import LayerProfile
from repro.core import split as SP
from repro.data.pipeline import DeviceClientStore
from repro.models.factory import Model
from repro.training.optim import make_optimizer
from repro.utils.trace import span


def pow2_bucket(n: int) -> int:
    """Round a segment's batch maximum up to the next power of two.

    The scan engine pads gather plans to ``pow2_bucket(b_max)`` columns so
    a reconfiguration sweep over batch maxima hits a bounded (log-sized)
    set of executables instead of one compile per distinct b_max; the
    extra columns carry loss-mask zeros and contribute exactly nothing
    (DESIGN.md §8).
    """
    return 1 << max(0, int(n) - 1).bit_length()


# ---------------------------------------------------------------------------
# Edge simulator
# ---------------------------------------------------------------------------

@dataclass
class SimResult:
    rounds: List[int] = field(default_factory=list)
    clock: List[float] = field(default_factory=list)      # simulated seconds
    train_loss: List[float] = field(default_factory=list)
    test_acc: List[float] = field(default_factory=list)
    test_loss: List[float] = field(default_factory=list)
    b_history: List[np.ndarray] = field(default_factory=list)
    cut_history: List[np.ndarray] = field(default_factory=list)

    def converged_time(self, window: int = 5, tol: float = 0.0002) -> float:
        """Paper's criterion: accuracy improves < tol over `window` evals."""
        acc = self.test_acc
        for k in range(window, len(acc)):
            if max(acc[k - window:k + 1]) - acc[k - window] < tol:
                return self.clock[k]
        return self.clock[-1] if self.clock else float("inf")


def clip_scale_from_norm(norm, clip: float):
    """min(1, clip/norm) — THE clip rule, shared by every engine so the
    legacy==vectorized==scan equivalence can't drift at the definition
    site."""
    return jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))


def clip_by_global_norm(grads, clip: float):
    """Scale a gradient tree so its global L2 norm is at most ``clip``.

    Applied per client before any HASFL update: plain SGD at the paper's
    gamma intermittently diverges on small per-client batches (loss spikes
    measured on the CPU-scale runs — DESIGN.md §2), and both execution
    paths must stabilize identically for the vectorized==legacy regression
    to hold.  ``clip=0`` disables.
    """
    if not clip:
        return grads
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))
    scale = clip_scale_from_norm(norm, clip)
    return jax.tree_util.tree_map(lambda l: (l * scale).astype(l.dtype), grads)


class SFLEdgeSimulator:
    """Paper-faithful edge simulation with three equivalent round engines.

    ``engine="vectorized"`` keeps one [N, ...]-stacked copy of every
    cuttable unit and runs each round as a single jitted step: a vmapped
    per-client grad, the Eq. 4 server-common mean update, the Eq. 5-6
    client-specific updates, and the every-I Eq. 7 aggregation folded in as
    a ``jnp.where`` on a traced flag (the same idiom as the SPMD pod step).
    ``engine="scan"`` goes one level further and runs an entire *segment*
    of rounds — up to the next eval/reconfiguration boundary — as one
    jitted ``lax.scan`` with donated carry over device-resident data
    (carry layout, donation, host-RNG index feeding, and b_max bucketing
    are specified in DESIGN.md §8); ``run()`` then acts as a segment
    scheduler and fetches per-round losses once per segment.
    ``engine="legacy"`` preserves the original per-client Python loop —
    the reference for the equivalence regression tests and the
    ``benchmarks/sim_speed.py`` comparison.  The pre-scan ``vectorized``
    bool is deprecated (DeprecationWarning): it still maps to
    ``"vectorized"``/``"legacy"`` when ``engine`` is unset.
    """

    def __init__(
        self, model: Model, sampler, test_batch: dict,
        devices: Sequence[DeviceProfile], sfl: SFLConfig,
        profile: LayerProfile, seed: int = 0,
        vectorized: Optional[bool] = None,
        engine: Optional[str] = None,
        conv_impl: Optional[str] = None,
        update_impl: Optional[str] = None,
        fault_mode: str = "soft",
        deadline_factor: float = 2.0,
        mesh=None,
        cohort_bank=None
    ):
        self.model = model
        self.cfg = model.cfg
        self.sampler = sampler
        self.test_batch = {k: jnp.asarray(v) for k, v in test_batch.items()}
        self.devices = list(devices)
        self.sfl = sfl
        self.profile = profile
        self.lat = LatencyModel(profile, devices, sfl)
        self.n = len(devices)
        self.available = np.ones(self.n, bool)
        self.rng = np.random.default_rng(seed)
        if vectorized is not None:
            # legacy bool from the pre-scan era: kept as an alias so old
            # drivers keep running, but the engine name is the real API
            warnings.warn(
                "SFLEdgeSimulator(vectorized=...) is deprecated; pass "
                "engine='vectorized'/'legacy' (or leave engine unset for "
                "the default) instead",
                DeprecationWarning, stacklevel=2)
            if engine is None:
                engine = "vectorized" if vectorized else "legacy"
        if engine is None:
            engine = "vectorized"
        if engine not in ("legacy", "vectorized", "scan"):
            raise ValueError(f"unknown round engine {engine!r}")
        self.engine = engine
        self.vectorized = engine != "legacy"
        # Mesh mode (DESIGN.md §15): shard the stacked client axis over
        # a device mesh with two-tier Eq. 4/7 aggregation.  Scan-engine
        # only (it is a layout statement over the scan executable), and
        # soft faults only in v1 (the dropout/deadline planners reason
        # over the flat barrier, not the tiered one).
        self.mesh_spec = mesh
        self._axis_name = None
        self._edge_size = None
        self._bank = None
        if mesh is not None:
            mesh.validated()
            if engine != "scan":
                raise ValueError("mesh mode needs engine='scan'")
            if fault_mode != "soft":
                raise ValueError(
                    "mesh mode v1 runs fault_mode='soft' — tiered "
                    "dropout/deadline planning is not implemented")
            if self.n % mesh.n_edges != 0:
                raise ValueError(
                    f"n_edges {mesh.n_edges} must divide the cohort "
                    f"size {self.n}")
        elif cohort_bank is not None:
            raise ValueError("cohort_bank rides mesh mode; pass mesh=")
        # Fault semantics (DESIGN.md §12): "soft" is the historical
        # resource-floor degradation (full participation, bit-for-bit);
        # "dropout" excludes unavailable clients (the churn/outage mask)
        # from the round; "deadline" additionally drops clients whose
        # Eq. 38 phase latency exceeds ``deadline_factor x`` the cohort
        # median, and advances the round clock at the deadline.
        if fault_mode not in ("soft", "dropout", "deadline"):
            raise ValueError(f"unknown fault_mode {fault_mode!r}")
        if fault_mode == "deadline" and not deadline_factor > 0:
            raise ValueError("deadline_factor must be > 0")
        self.fault_mode = fault_mode
        self.deadline_factor = float(deadline_factor)
        # Kernel knobs (DESIGN.md §11).  ``conv_impl`` switches the
        # vectorized/scan engines' per-client grads from vmap-of-grad
        # (whose batched-weight convs lower to XLA CPU's slow grouped
        # convs) to grad-of-sum over the model's stacked loss, with the
        # convolutions routed through `kernels.ops.batched_conv`.  The
        # user-facing value "kernel" means the backend-dispatched fast
        # path (ops impl "auto": Pallas on TPU, im2col on CPU); None
        # keeps the bitwise oracle.  The legacy engine ignores both (it
        # has no stacked state).  ``update_impl`` likewise routes
        # `split.hasfl_round_update` through the fused clip+SGD kernel.
        if conv_impl is not None and getattr(model, "stacked_loss", None) is None:
            raise ValueError(
                f"conv_impl={conv_impl!r} needs a model with a stacked "
                "loss (CNN family); this model has none")
        self.conv_impl = conv_impl
        self.update_impl = update_impl
        self._conv_ops_impl = {"kernel": "auto"}.get(conv_impl, conv_impl)
        self._update_ops_impl = {"kernel": "auto"}.get(update_impl, update_impl)

        params = model.init(jax.random.PRNGKey(seed))
        units, self.rebuild = SP.to_units(self.cfg, params)
        self.units = units
        # per-client copies of every *cuttable* unit; shared tail managed by
        # L_c at update time.  Memory: N copies of a small model (sim only).
        if self.vectorized:
            self._stacked = SP.replicate_units(units, self.n)
        else:
            self._client_units = [
                jax.tree_util.tree_map(jnp.copy, units)
                for _ in range(self.n)
            ]

        def _clipped_grad(units, batch):
            (loss, aux), g = jax.value_and_grad(self._loss, has_aux=True)(units, batch)
            return (loss, aux), clip_by_global_norm(g, self.sfl.clip_norm)

        # clip inside the jitted grad so the legacy engine pays no eager
        # per-client dispatch the vectorized engine doesn't
        self._grad_fn = jax.jit(_clipped_grad)
        self._eval_fn = jax.jit(self._eval)
        # the previous stacked state is dead after each round/segment, so
        # donate it and let XLA update in place instead of copying [N, ...]
        self._round_fn = jax.jit(self._vectorized_round, donate_argnums=(0,))
        if engine == "scan":
            self.store = DeviceClientStore.from_sampler(sampler)
            self._scan_fn = jax.jit(self._scan_segment, donate_argnums=(0,))
        if mesh is not None:
            from repro.mesh.sharded import build_device_mesh, \
                make_sharded_scan

            self._device_mesh = build_device_mesh(mesh, self.n)
            self._axis_name = mesh.axis
            self._edge_size = self.n // mesh.n_edges
            self._scan_fn = make_sharded_scan(
                self, self._device_mesh, mesh.axis)
            if cohort_bank is not None:
                self._bank = cohort_bank
                cohort_bank.attach(self)

    @property
    def client_units(self):
        """Per-client unit lists.

        When vectorized this is a read-only snapshot unstacked from the
        [N, ...] representation, returned as nested tuples so that
        item-assignment (which could never write back to the stacked
        state) raises instead of silently no-opping; construct with
        ``engine="legacy"`` to patch client parameters in place.
        """
        if self.vectorized:
            return tuple(
                tuple(units)
                for units in SP.unstack_unit_trees(self._stacked, self.n)
            )
        return self._client_units

    # -- loss over unit list -------------------------------------------------
    def _loss(self, units, batch):
        params = self.rebuild(units)
        return self.model.loss(params, batch)

    def _eval(self, units, batch):
        params = self.rebuild(units)
        logits, _ = self.model.apply(params, batch)
        labels = batch["labels"]
        if logits.ndim == 3:
            pred = logits.argmax(-1)
            acc = (pred == labels).mean()
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            loss = -jnp.take_along_axis(logp, labels[..., None], -1).mean()
        else:
            acc = (logits.argmax(-1) == labels).mean()
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            loss = -jnp.take_along_axis(logp, labels[:, None], 1).mean()
        return loss, acc

    # -- unit-space helpers ---------------------------------------------------
    def _unit_cuts(self, cuts_layers: np.ndarray) -> np.ndarray:
        return np.asarray([
            SP.layer_cut_to_unit_cut(self.cfg, int(c))
            for c in cuts_layers
        ], int)

    def _client_slice(self, l_c_units: int):
        """Unit indices belonging to the client-specific (every-I) part."""
        if self.cfg.family == CNN:
            return list(range(l_c_units))
        return list(range(0, l_c_units + 1))   # embed + first l_c reps

    # -- round engines --------------------------------------------------------
    def _client_grads(self, stacked, batch):
        """Vmapped per-client (loss, raw grad, clip scale) over stacked
        units.  The clip factor is returned separately (same math as
        ``clip_by_global_norm``) so the round update can fuse it into its
        single pass over the gradients instead of materializing a scaled
        copy of the whole gradient tree.

        With ``conv_impl`` set, the vmap-of-grad is replaced by one grad
        of the *sum* of the model's stacked per-client losses — exact
        (client i's stacked slice only touches loss i), and it keeps the
        convolutions inside `ops.batched_conv`'s custom_vjp instead of
        the vmapped-weights lowering."""
        clip = self.sfl.clip_norm

        if self.conv_impl is not None:
            def total(st):
                losses = self.model.stacked_loss(
                    st, batch, impl=self._conv_ops_impl)
                return losses.sum(), losses

            grads, losses = jax.grad(total, has_aux=True)(stacked)
        else:
            def per_client(units, b):
                (loss, _), g = jax.value_and_grad(
                    self._loss, has_aux=True)(units, b)
                return loss, g

            losses, grads = jax.vmap(per_client)(stacked, batch)
        scale = None
        if clip:
            norm = jnp.sqrt(
                sum(
                    jnp.sum(
                        jnp.square(l.astype(jnp.float32)),
                        axis=tuple(range(1, l.ndim)),
                    )
                    for l in jax.tree_util.tree_leaves(grads)
                )
            )
            scale = clip_scale_from_norm(norm, clip)
        return losses, grads, scale

    def _vectorized_round(self, stacked, batch, masks, do_agg, part=None):
        """One HASFL round over [N, ...]-stacked units (jitted).

        Fuses: vmapped per-client grads (with per-client clipping) and the
        Eq. 4 / 5-6 / 7 update rule (`split.hasfl_round_update`, shared
        with the scan engine) — unit membership, the aggregation flag,
        and the per-round participation vector are traced, so one
        executable covers every (cut, round, fault) combination at a
        given batch shape.
        """
        with jax.named_scope("client_grads"):
            losses, grads, scale = self._client_grads(stacked, batch)
        with jax.named_scope("update"):
            new_stacked = SP.hasfl_round_update(
                stacked, grads, masks, do_agg,
                self.sfl.lr, grad_scale=scale, impl=self._update_ops_impl,
                participation=part,
                axis_name=self._axis_name, edge_size=self._edge_size
            )
        return new_stacked, losses

    def _scan_segment(self, stacked, t0, idx_seg, row_mask, masks, arrays,
                      parts=None):
        """Run a whole segment of rounds as one jitted ``lax.scan``.

        Carry: (stacked units, absolute round counter).  Per step: gather
        the padded per-client batch on device from the segment's
        pre-drawn ``[R, N, b_pad]`` index plan, run the shared round body,
        and derive the every-I Eq. 7 flag from the traced counter.  The
        per-round client losses come back as the scan ``ys`` — one host
        fetch per segment instead of per round.  ``parts`` is the
        segment's pre-computed ``[R, N]`` participation plan (None on the
        full-cohort soft path).  (DESIGN.md §8, §12.)
        """
        interval = self.sfl.agg_interval

        def step(carry, xs):
            stacked, t = carry
            idx_r, part_r = xs
            t1 = t + 1
            with jax.named_scope("gather"):
                batch = DeviceClientStore.device_batch(arrays, idx_r, row_mask)
            new_stacked, losses = self._vectorized_round(
                stacked, batch, masks, (t1 % interval) == 0, part_r)
            return (new_stacked, t1), losses

        (stacked, _), losses = jax.lax.scan(
            step, (stacked, t0), (idx_seg, parts))
        return stacked, losses

    def _legacy_round(self, b, cuts, client_idx, do_agg, part=None):
        """The original per-client Python loop (seed implementation) —
        kept as the reference engine for the equivalence regression and
        the sim_speed benchmark.  ``part`` ([N] float or None) excludes
        dropped clients from every mean and holds their client-specific
        params (the loop-form twin of the stacked participation
        semantics in `split.hasfl_round_update`)."""
        gamma = self.sfl.lr
        b_max = int(np.max(b))
        losses = []
        grads_all = []
        for i in range(self.n):
            batch = self.sampler.sample(i, int(b[i]), pad_to=b_max)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            (loss, _), g = self._grad_fn(self._client_units[i], batch)
            # keep the loss on device — a float() here would block the
            # dispatch queue once per client per round; run() fetches the
            # stacked losses only at eval boundaries
            losses.append(loss)
            grads_all.append(g)

        if part is None:
            members = list(range(self.n))
        else:
            members = [i for i in range(self.n) if part[i] > 0]
        cnt = len(members)

        # server-common units (> L_c): averaged update, every round (Eq.4)
        # over the participating clients only; a drop-everyone round holds
        # params.  Base = client mean, matching the vectorized engine
        # (identical to any single copy while the units are synchronized;
        # correct when a reconfiguration moves a still-diverged unit to
        # the server side).
        if cnt:
            for u in range(len(self.units)):
                if u in client_idx:
                    continue
                mean_g = jax.tree_util.tree_map(
                    lambda *gs: sum(gs) / cnt,
                    *[grads_all[i][u] for i in members])
                mean_p = jax.tree_util.tree_map(
                    lambda *xs: sum(xs) / cnt,
                    *[self._client_units[i][u] for i in members])
                new_common = jax.tree_util.tree_map(
                    lambda p, g: p - gamma * g.astype(p.dtype),
                    mean_p, mean_g)
                for i in range(self.n):
                    self._client_units[i][u] = new_common

        # client-specific units (<= L_c): individual updates (Eq.5-6),
        # participants only — dropped clients hold their params
        for i in members:
            for u in client_idx:
                self._client_units[i][u] = jax.tree_util.tree_map(
                    lambda p, g: p - gamma * g.astype(p.dtype),
                    self._client_units[i][u], grads_all[i][u])

        # client-side aggregation stage, every I (Eq.7): survivor mean,
        # broadcast to everyone (a dropped client re-syncs on the next
        # aggregation broadcast)
        if do_agg and cnt:
            for u in client_idx:
                mean_u = jax.tree_util.tree_map(
                    lambda *xs: sum(xs) / cnt,
                    *[self._client_units[i][u] for i in members])
                for i in range(self.n):
                    self._client_units[i][u] = mean_u
        return jnp.stack(losses)

    # -- scenario injection ---------------------------------------------------
    def set_devices(self, devices: Sequence[DeviceProfile], available=None) -> None:
        """Inject the current (possibly trace-evolved) device pool.

        Updates the latency model in place so both the wall-clock
        accounting and any controller reading ``sim.devices`` at the next
        reconfiguration boundary observe the same environment state.  The
        pool size must stay N (fixed-cohort formulation; churn is modeled
        as outage — DESIGN.md §9).
        """
        if len(devices) != self.n:
            raise ValueError(f"device pool must stay size {self.n}, got {len(devices)}")
        self.devices = list(devices)
        self.lat.set_devices(self.devices)
        self.available = (
            np.ones(self.n, bool) if available is None
            else np.asarray(available, bool)
        )

    def _scenario_tick(self, scenario, t: int) -> None:
        """Advance the environment to round ``t``'s trace state."""
        if scenario is not None:
            self.set_devices(scenario.profiles_at(t), scenario.available_at(t))

    def _fault_round(self, b, cuts):
        """(participation, t_split, t_agg) for one round on the CURRENT
        injected device state, under the active fault mode.

        ``participation`` is None on the soft path (full cohort, the
        historical bitwise clock), an [N] float32 vector otherwise; the
        times already account for the fault semantics (survivor-only
        straggler maxes, deadline-capped barriers — `core.latency`).
        """
        if self.fault_mode == "soft":
            if self.mesh_spec is not None and self.mesh_spec.tiered_latency:
                ts, ta = self.lat.tiered_round(
                    b, cuts, self.mesh_spec.n_edges,
                    edge_flops=self.mesh_spec.edge_flops,
                    edge_bw=self.mesh_spec.edge_bw)
                return None, ts, ta
            return None, self.lat.t_split(b, cuts), self.lat.t_agg(b, cuts)
        if self.fault_mode == "dropout":
            part = np.asarray(self.available, bool)
            ts, ta = self.lat.masked_round(b, cuts, part)
            return part.astype(np.float32), ts, ta
        part, ts, ta = self.lat.deadline_round(
            b, cuts, np.asarray(self.available, bool), self.deadline_factor)
        return part.astype(np.float32), ts, ta

    # -- main loop ------------------------------------------------------------
    def run(
        self, policy_fn: Callable, rounds: int, eval_every: int = 10,
        reconfigure_every: Optional[int] = None,
        verbose: bool = False, scenario=None,
        checkpoint_every: int = 0, snapshot_cb=None, resume=None,
        traffic=None
    ) -> SimResult:
        """policy_fn(sim, rng) -> (b [N], cuts_layers [N]).

        ``scenario`` (a `repro.scenarios.Scenario`) makes the environment
        time-varying: each round's latency is evaluated on that round's
        trace state, and the state is left injected when ``policy_fn``
        fires at a reconfiguration boundary — closing the control loop
        (observe -> re-optimize -> apply) for every engine.

        ``checkpoint_every`` makes every multiple of it a segment
        boundary and fires ``snapshot_cb(t, clock, b, cuts, res)`` there
        (after any reconfiguration/eval, so the snapshot captures the
        exact mid-run host state); ``resume`` is a dict from a restored
        snapshot (`Session.resume` assembles it) that continues the run
        bitwise-identically from its round.  Both are segment-boundary
        objects: scan engine only.

        ``traffic`` (a `repro.traffic.TrafficPlane`) switches the run to
        semi-async streaming mode: the plane's event walk replaces the
        barriered Eq. 38 clock, per-round staleness weights ride the
        participation lane, and cohort churn rewrites store slots at
        segment boundaries.  ``traffic=None`` is the synchronous path,
        bit-for-bit unchanged (the tier-1 gate).  Scan engine only.
        Checkpoint/resume composes: the Session snapshot carries the
        plane's host state (slot/pool bindings, event heap, population
        cursor) alongside the params (DESIGN.md §14/§15).
        """
        reconf = reconfigure_every or self.sfl.agg_interval
        if traffic is not None:
            if self.engine != "scan":
                raise ValueError("traffic mode needs engine='scan'")
            return self._run_traffic(
                policy_fn, rounds, eval_every, reconf, verbose, scenario,
                traffic, checkpoint_every, snapshot_cb, resume)
        if self.engine == "scan":
            return self._run_scan(
                policy_fn, rounds, eval_every, reconf,
                verbose, scenario, checkpoint_every, snapshot_cb, resume
            )
        if checkpoint_every or snapshot_cb or resume is not None:
            raise ValueError(
                "checkpoint/resume snapshots are segment-boundary objects "
                "— engine='scan' only")
        res = SimResult()
        clock = 0.0
        self._scenario_tick(scenario, 0)
        b, cuts = policy_fn(self, self.rng)
        self._record_policy(res, b, cuts)
        n_units_total = len(self.units)

        for t in range(1, rounds + 1):
            ucuts = self._unit_cuts(np.asarray(cuts))
            l_c_units = int(np.max(ucuts))
            do_agg = (t % self.sfl.agg_interval) == 0

            # round t runs (and is priced) against round t's trace state
            self._scenario_tick(scenario, t)
            part, t_split, t_agg = self._fault_round(b, cuts)

            # --- split-training round (a1-a5) + every-I stage (b1-b3) -----
            if self.vectorized:
                b_max = int(np.max(b))
                per = [
                    self.sampler.sample(i, int(b[i]), pad_to=b_max)
                    for i in range(self.n)
                ]
                batch = {k: jnp.asarray(np.stack([p[k] for p in per])) for k in per[0]}
                masks = jnp.asarray(
                    SP.client_unit_mask(self.cfg, n_units_total, l_c_units)
                )
                self._stacked, losses = self._round_fn(
                    self._stacked, batch, masks, jnp.asarray(do_agg),
                    None if part is None else jnp.asarray(part)
                )
            else:
                client_idx = self._client_slice(l_c_units)
                losses = self._legacy_round(b, cuts, client_idx, do_agg, part)

            clock += t_split
            if do_agg:
                clock += t_agg

            b, cuts = self._maybe_reconfigure(
                res, policy_fn, t, reconf,
                rounds, b, cuts
            )
            if t % eval_every == 0 or t == rounds:
                self._record_metrics(res, t, clock, losses, verbose)
        return res

    # -- run() scaffolding shared by the per-round loop and the segment
    # scheduler: any change here changes both paths, keeping the
    # scan==vectorized equivalence contract in one place --------------------
    def _record_policy(self, res: SimResult, b, cuts) -> None:
        res.b_history.append(np.asarray(b).copy())
        res.cut_history.append(np.asarray(cuts).copy())

    def _maybe_reconfigure(
        self, res: SimResult, policy_fn: Callable,
        t: int, reconf: int, rounds: int, b, cuts
    ):
        """Reconfiguration (Algorithm 1 line 23)."""
        if t % reconf == 0 and t < rounds:
            b, cuts = policy_fn(self, self.rng)
            self._record_policy(res, b, cuts)
        return b, cuts

    def _advance_clock(
        self, clock: float, t: int, nxt: int, b, cuts,
        scenario=None
    ) -> float:
        """Walk rounds (t, nxt] on the host wall clock.

        Shared by the scan-engine segment scheduler and the
        ``repro.api`` grid runner so both accumulate bitwise-identical
        float sums; static pools hoist the per-round latency out of the
        loop, a scenario re-evaluates it on each round's trace state.
        """
        if scenario is None:
            _, t_split, t_agg = self._fault_round(b, cuts)
            for r in range(t + 1, nxt + 1):
                clock += t_split
                if r % self.sfl.agg_interval == 0:
                    clock += t_agg
        else:
            for r in range(t + 1, nxt + 1):
                self._scenario_tick(scenario, r)
                _, t_split, t_agg = self._fault_round(b, cuts)
                clock += t_split
                if r % self.sfl.agg_interval == 0:
                    clock += t_agg
        return clock

    def _record_metrics(
        self, res: SimResult, t: int, clock: float,
        losses, verbose: bool, live=None, seg_t: Optional[int] = None
    ) -> None:
        """Eval + metric append; the only host fetch of ``losses``.

        ``live`` ([N] bool, traffic mode) restricts both the aggregate
        model and the train-loss mean to occupied slots — empty slots
        train a weight-0 dummy batch whose loss is meaningless.
        ``seg_t`` is the first round of the segment that ends here, the
        ``t`` arg of its spans (the eval round ``t`` when None).
        """
        seg_t = t if seg_t is None else seg_t
        with span("aggregate", t=seg_t):
            agg = self._aggregate_model(live)
        with span("eval", t=seg_t):
            tl, ta = self._eval_fn(agg, self.test_batch)
        losses = np.asarray(losses)
        if live is not None and live.any():
            losses = losses[np.asarray(live, bool)]
        mean_loss = float(np.mean(losses))
        with span("eval_fetch", t=seg_t):
            tl, ta = float(tl), float(ta)
        res.rounds.append(t)
        res.clock.append(clock)
        res.train_loss.append(mean_loss)
        res.test_loss.append(tl)
        res.test_acc.append(ta)
        if verbose:
            print(
                f"round {t:5d} clock {clock:9.1f}s "
                f"loss {mean_loss:.4f} "
                f"acc {ta:.4f}", flush=True
            )

    def _dispatch_counts(self, b, b_pad: int, rounds: int) -> dict:
        """Counters of a segment's ``repro.dispatch`` span: ``rows`` (real
        rows), ``padded_rows`` (rows the executable computes) and, for an
        encoder-decoder, ``frames`` (the real rows' encoder positions),
        summed over the segment's rounds."""
        rows = int(np.sum(b)) * rounds
        out = {"rows": rows, "padded_rows": self.n * b_pad * rounds}
        if self.cfg.is_enc_dec:
            out["frames"] = rows * self.cfg.encoder_seq
        return out

    def _segment_participation(self, t: int, nxt: int, b, cuts, scenario):
        """Pre-compute the ``[R, N]`` participation plan for rounds
        (t, nxt] by walking each round's trace state host-side (the same
        states and order `_advance_clock` re-walks — scenario history is
        cached, so both see identical floats).  None on the soft path."""
        if self.fault_mode == "soft":
            return None
        plan = []
        for r in range(t + 1, nxt + 1):
            self._scenario_tick(scenario, r)
            p_r, _, _ = self._fault_round(b, cuts)
            plan.append(p_r)
        return jnp.asarray(np.stack(plan))

    def _run_scan(
        self, policy_fn: Callable, rounds: int, eval_every: int,
        reconf: int, verbose: bool, scenario=None,
        checkpoint_every: int = 0, snapshot_cb=None, resume=None
    ) -> SimResult:
        """Segment scheduler for the scan engine.

        Chops the round range at eval / reconfiguration / checkpoint
        boundaries (the every-I stage needs no boundary — it runs inside
        the scan on the traced counter), pre-draws each segment's gather
        plan from the authoritative host RNG, and dispatches one donated
        scan per segment.  Metrics, clock accounting, and policy calls
        replicate the per-round engines exactly — under a scenario the
        clock walks the segment's rounds against the same per-round trace
        states (and float summation order) the per-round engines use.
        Segment boundaries do not change numerics (a split ``lax.scan``
        runs the same per-round ops on the same carry), which is what
        makes checkpointed and resumed runs bitwise-identical to an
        uninterrupted one.
        """
        ckpt = int(checkpoint_every or 0)
        if resume is not None:
            res = resume["res"]
            clock = float(resume["clock"])
            t = int(resume["t"])
            b = np.asarray(resume["b"])
            cuts = np.asarray(resume["cuts"])
            # params/RNG streams were restored onto self by the caller;
            # re-inject the snapshot round's trace state (the scenario
            # regenerates its history deterministically from the seed)
            self._scenario_tick(scenario, t)
        else:
            res = SimResult()
            clock = 0.0
            t = 0
            self._scenario_tick(scenario, 0)
            b, cuts = policy_fn(self, self.rng)
            self._record_policy(res, b, cuts)
        n_units_total = len(self.units)

        while t < rounds:
            nxt = min(
                (t // eval_every + 1) * eval_every,
                (t // reconf + 1) * reconf, rounds
            )
            if ckpt:
                nxt = min(nxt, (t // ckpt + 1) * ckpt)
            t0 = t
            with span("segment", t=t0, rounds=nxt - t0):
                with span("plan", t=t0):
                    ucuts = self._unit_cuts(np.asarray(cuts))
                    l_c_units = int(np.max(ucuts))
                    masks = jnp.asarray(SP.client_unit_mask(
                        self.cfg, n_units_total, l_c_units))
                    b_pad = pow2_bucket(int(np.max(b)))
                    idx = self.store.segment_indices(nxt - t, b, b_pad)
                    row_mask = self.store.row_mask(b, b_pad)
                    parts = self._segment_participation(
                        t, nxt, b, cuts, scenario)
                with span("dispatch", t=t0, **self._dispatch_counts(
                        b, b_pad, nxt - t)):
                    self._stacked, seg_losses = self._scan_fn(
                        self._stacked, jnp.asarray(t, jnp.int32), idx,
                        row_mask, masks, self.store.arrays, parts)

                # clock: accumulate round-by-round on host (bitwise-
                # identical float summation to the per-round engines)
                with span("clock", t=t0):
                    clock = self._advance_clock(
                        clock, t, nxt, b, cuts, scenario)
                t = nxt

                if self._bank is not None and t < rounds \
                        and t % self.sfl.agg_interval == 0:
                    # cohort rotation at the agg-aligned boundary: the
                    # departing cohort's state is already folded into
                    # the Eq. 7 broadcast, so the bank swaps
                    # pools/profiles and re-broadcasts the aggregate
                    # (DESIGN.md §15)
                    self._bank.rotate(self, t)
                with span("control", t=t0):
                    b, cuts = self._maybe_reconfigure(
                        res, policy_fn, t, reconf,
                        rounds, b, cuts
                    )
                if t % eval_every == 0 or t == rounds:
                    # one [R, N] loss fetch per segment; the eval round
                    # is the segment's last, so its losses are the final
                    # ys row
                    with span("fetch", t=t0):
                        last = np.asarray(seg_losses)[-1]
                    self._record_metrics(res, t, clock, last, verbose,
                                         seg_t=t0)
            if ckpt and snapshot_cb is not None and t % ckpt == 0:
                # after reconfigure/eval: the snapshot captures the
                # decisions and metrics exactly as the resumed loop needs
                snapshot_cb(t, clock, b, cuts, res)
        return res

    def _run_traffic(
        self, policy_fn: Callable, rounds: int, eval_every: int,
        reconf: int, verbose: bool, scenario, traffic,
        checkpoint_every: int = 0, snapshot_cb=None, resume=None
    ) -> SimResult:
        """Segment scheduler for the semi-async streaming mode.

        Structure mirrors `_run_scan` — same boundaries, same scan
        executable — with three substitutions (DESIGN.md §14): the
        per-round participation plan comes from the plane's event walk
        (staleness weights, never None), the wall clock is the plane's
        virtual clock (no Eq. 38 barrier), and segment boundaries run
        the plane's admit/evict slot surgery before the policy fires.
        Empty slots train the 1-sample dummy batch at weight zero, so
        every array shape matches the fixed-cohort run and the scan
        executable is shared.

        Checkpointing mirrors `_run_scan` too: ckpt multiples become
        segment boundaries and the snapshot fires after the boundary's
        surgery/injection/reconfigure — the Session folds the plane's
        host state (`TrafficPlane.state`) into the same snapshot, so a
        resumed run replays the identical event walk.
        """
        ckpt = int(checkpoint_every or 0)
        if resume is not None:
            res = resume["res"]
            t = int(resume["t"])
            b = np.asarray(resume["b"])
            cuts = np.asarray(resume["cuts"])
            # plane state (clock, heap, slots, pools, population cursor)
            # was restored by the caller before run(); attach only
            # validates wiring and re-derives the construction pool
            traffic.attach(self, scenario, resume=True)
            traffic.inject_profiles(self, scenario, t)
        else:
            res = SimResult()
            traffic.attach(self, scenario)
            traffic.inject_profiles(self, scenario, 0)
            t = 0
            b, cuts = policy_fn(self, self.rng)
            self._record_policy(res, b, cuts)
        n_units_total = len(self.units)

        while t < rounds:
            nxt = min(
                (t // eval_every + 1) * eval_every,
                (t // reconf + 1) * reconf, rounds
            )
            if ckpt:
                nxt = min(nxt, (t // ckpt + 1) * ckpt)
            ucuts = self._unit_cuts(np.asarray(cuts))
            l_c_units = int(np.max(ucuts))
            masks = jnp.asarray(
                SP.client_unit_mask(self.cfg, n_units_total, l_c_units))
            b_eff = traffic.effective_batches(b)
            b_pad = pow2_bucket(int(np.max(b_eff)))
            idx = self.store.segment_indices(nxt - t, b_eff, b_pad)
            row_mask = self.store.row_mask(b_eff, b_pad)
            parts = jnp.asarray(
                traffic.plan_segment(self, scenario, t, nxt, b_eff, cuts))
            self._stacked, seg_losses = self._scan_fn(
                self._stacked, jnp.asarray(t, jnp.int32), idx, row_mask,
                masks, self.store.arrays, parts)
            t = nxt

            traffic.apply_boundary(self, t)
            # the policy observes round-t resources for the *new* cohort
            traffic.inject_profiles(self, scenario, t)
            b, cuts = self._maybe_reconfigure(
                res, policy_fn, t, reconf, rounds, b, cuts)
            if t % eval_every == 0 or t == rounds:
                self._record_metrics(
                    res, t, traffic.clock, np.asarray(seg_losses)[-1],
                    verbose, live=traffic.live_mask())
            if ckpt and snapshot_cb is not None and t % ckpt == 0:
                snapshot_cb(t, traffic.clock, b, cuts, res)
        return res

    def _aggregate_model(self, live=None):
        """Virtual aggregated model w̄ (analysis object, Sec. IV).

        ``live`` ([N] bool, traffic mode) means over occupied slots only
        (all-slot mean when every/no slot is live — empty slots track
        the broadcast, so the two agree in the degenerate cases)."""
        if self.vectorized:
            if live is not None:
                live = np.asarray(live, bool)
                if live.any() and not live.all():
                    sel = jnp.asarray(np.flatnonzero(live))
                    return [
                        jax.tree_util.tree_map(
                            lambda a: a[sel].mean(axis=0), u)
                        for u in self._stacked
                    ]
            return SP.mean_unit_trees(self._stacked)
        return [
            jax.tree_util.tree_map(
                lambda *xs: sum(xs) / self.n,
                *[self._client_units[i][u] for i in range(self.n)],
            )
            for u in range(len(self.units))
        ]


# ---------------------------------------------------------------------------
# SPMD pod train step (the dry-run object)
# ---------------------------------------------------------------------------

def make_hasfl_train_step(
    model: Model, *, n_clients: int, cut_reps: int,
    agg_interval: int, optimizer_name: str = "adam",
    lr: float = 3e-4, optimizer_dtype: str = "float32",
    grad_accum: int = 1, remat: bool = True,
    shard_fn=None, unroll: bool = False,
    param_shardings=None, rep_shard_fn=None
):
    """Build (init_state, train_step) for the production SPMD path.

    State: {"client": per-client stacked prefix [N, ...], "server": suffix,
            "opt": optimizer state, "step": scalar}.
    Batch: {"tokens": [N, b, S], "labels": [N, b, S], (stubs...)}.

    Semantics per HASFL: server part's gradient is the client-mean (Eq. 4,
    every step); client parts take their own gradients (Eq. 5-6) and are
    averaged every ``agg_interval`` steps (Eq. 7) inside the step.

    ``param_shardings``: optional ({client shardings}, {server shardings})
    NamedSharding trees; when given, accumulated gradients are explicitly
    constrained to the parameter layout (the optimization_barrier between
    microbatches blocks GSPMD propagation, which otherwise leaves the big
    MoE grad buffers unsharded).
    """
    opt = make_optimizer(optimizer_name, lr, state_dtype=optimizer_dtype)

    def init_state(rng):
        params = model.init(rng)
        client, server = SP.split_stacked(params, cut_reps)
        client_stacked = SP.replicate_client(client, n_clients)
        state = {
            "client": client_stacked, "server": server,
            "step": jnp.zeros((), jnp.int32)
        }
        state["opt"] = opt.init({"client": client_stacked, "server": server})
        return state

    def per_client_loss(client_i, server, batch_i):
        params = SP.merge_stacked(client_i, server)
        loss, _ = model.loss(
            params, batch_i, shard_fn=shard_fn, remat=remat,
            unroll=unroll, rep_shard_fn=rep_shard_fn
        )
        return loss

    def mean_loss(client_stacked, server, batch):
        if getattr(model, "split_loss", None) is not None:
            # faithful split dataflow: per-client prefix, concatenated
            # server batch (also avoids materializing per-client server
            # gradients — see factory.split_loss docstring)
            loss, _ = model.split_loss(
                client_stacked, server, batch, shard_fn=shard_fn,
                remat=remat, unroll=unroll, rep_shard_fn=rep_shard_fn)
            return loss
        losses = jax.vmap(per_client_loss, in_axes=(0, None, 0))(
            client_stacked, server, batch)
        return losses.mean()

    grad_fn = jax.value_and_grad(mean_loss, argnums=(0, 1))

    def train_step(state, batch):
        client, server = state["client"], state["server"]

        if grad_accum > 1:
            # Accumulate with lax.scan: the carry (grad trees) is
            # double-buffered by XLA, forcing sequential microbatches and
            # bounded live memory.  (A fori_loop here made the SPMD
            # partitioner blow up on large MoE models: >30 min compiles;
            # python-unrolling compiled fast but XLA scheduled all
            # microbatches' activations concurrently — scan gives both
            # fast compiles and bounded memory.)
            def constrain(gc_, gs_):
                if param_shardings is None:
                    return gc_, gs_
                gc_ = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, gc_,
                    param_shardings[0])
                gs_ = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, gs_,
                    param_shardings[1])
                return gc_, gs_

            def micro_step(carry, mb):
                gc, gs, ls = carry
                l, (gci, gsi) = grad_fn(client, server, mb)
                add = lambda a, b: a + b
                ngc = jax.tree_util.tree_map(add, gc, gci)
                ngs = jax.tree_util.tree_map(add, gs, gsi)
                ngc, ngs = constrain(ngc, ngs)
                return (ngc, ngs, ls + l), None

            # reshape [N, b, ...] -> [accum, N, b/accum, ...]
            def to_micro(x):
                n, b = x.shape[0], x.shape[1]
                xs = x.reshape(n, grad_accum, b // grad_accum, *x.shape[2:])
                return jnp.moveaxis(xs, 1, 0)

            micro_xs = jax.tree_util.tree_map(to_micro, batch)
            zeros_c = jax.tree_util.tree_map(jnp.zeros_like, client)
            zeros_s = jax.tree_util.tree_map(jnp.zeros_like, server)
            zeros_c, zeros_s = constrain(zeros_c, zeros_s)
            (gc, gs, loss), _ = jax.lax.scan(
                micro_step, (zeros_c, zeros_s, 0.0), micro_xs,
                unroll=grad_accum if unroll else 1)
            scale = 1.0 / grad_accum
            gc = jax.tree_util.tree_map(lambda x: x * scale, gc)
            gs = jax.tree_util.tree_map(lambda x: x * scale, gs)
            loss = loss * scale
        else:
            loss, (gc, gs) = grad_fn(client, server, batch)

        # mean_loss scales each client's grad by 1/N; restore per-client SGD
        gc = jax.tree_util.tree_map(lambda x: x * n_clients, gc)

        grads = {"client": gc, "server": gs}
        params = {"client": client, "server": server}
        new_params, new_opt = opt.update(grads, state["opt"], params, state["step"])

        # every-I aggregation of the client-stacked prefix (Eq. 7) — the
        # same traced-select idiom as the vectorized edge simulator
        step1 = state["step"] + 1
        do_agg = (step1 % agg_interval) == 0
        new_client = SP.aggregate_where(new_params["client"], do_agg)
        return {
            "client": new_client, "server": new_params["server"],
            "opt": new_opt, "step": step1
        }, {"loss": loss}

    return init_state, train_step
