"""`Session`: one place that assembles and runs an `ExperimentSpec`.

Every driver used to repeat the same 8-step wiring — config, model,
data, partition, sampler, `SFLConfig`, layer profile, device pool,
simulator, optimizer/policy — with small copy-paste drifts between
`benchmarks/common.py`, `repro.launch.train`, the examples, and the
scenario sweep.  A `Session` owns that assembly: construct it from a
spec, call `run()`, or hand a whole grid of specs to
`Session.run_grid` and compatible cells execute as vmapped mega-runs
(`repro.api.grid`).

Sessions are single-shot: the simulator they wrap is stateful (trained
parameters, advanced RNG streams), so a second `run()` would not be the
run the spec describes.  Build a fresh `Session` (cheap) per run.
"""

from __future__ import annotations

import dataclasses
import json
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import policies as policy_registry
from repro.api.grid import group_cells, run_group
from repro.api.spec import ExperimentSpec
from repro.config import get_config
from repro.core.bcd import HASFLOptimizer
from repro.core.latency import sample_devices
from repro.core.profiles import model_profile
from repro.core.sfl import SFLEdgeSimulator, SimResult, pow2_bucket
from repro.data import (
    ClientSampler,
    make_asr_data,
    make_cifar_like,
    make_lm_data,
    partition_iid,
    partition_noniid_shards,
)
from repro.models import build_model
from repro.training import checkpoint as ckpt


class Session:
    """One runnable simulation cell, assembled from an `ExperimentSpec`.

    Construction replicates the historical `benchmarks/common.make_sim`
    wiring exactly — one host RNG seeded from ``spec.seed`` feeds the
    partition, the sampler, and the device pool in that order — so specs
    reproduce the results every pre-API driver produced.
    """

    def __init__(self, spec: ExperimentSpec):
        spec = spec.validated()
        self.spec = spec
        self.cfg = get_config(spec.arch)
        base_policy, _ = policy_registry.parse_policy(spec.policy)
        if base_policy not in policy_registry.list_policies():
            raise KeyError(
                f"unknown policy {spec.policy!r}; "
                f"known: {policy_registry.list_policies()}"
            )
        if spec.scenario is not None:
            from repro.scenarios import list_presets

            if spec.scenario not in list_presets():
                raise KeyError(
                    f"unknown scenario preset {spec.scenario!r}; "
                    f"known: {list_presets()}"
                )

        self.model = build_model(self.cfg)
        rng = np.random.default_rng(spec.seed)
        train, test, shard_labels = self._build_data(spec)
        self._bank = None
        if spec.traffic is None:
            if spec.mesh is not None and spec.mesh.population is not None:
                # cohort-bank scale-out (DESIGN.md §15): the resident
                # simulator holds only the active cohort; every slot's
                # data pool is bound by the bank at attach/rotate time
                # (the same `set_pool` surgery traffic churn uses), so
                # the static partition over the logical population is
                # never materialized
                from repro.mesh.bank import CohortBank
                from repro.traffic.store import dummy_pool

                self.sampler = ClientSampler(
                    train, [dummy_pool() for _ in range(spec.n_clients)],
                    rng)
                self._bank = CohortBank(
                    spec.mesh, n_resident=spec.n_clients,
                    n_train=spec.n_train)
            elif spec.partition == "iid":
                shards = partition_iid(spec.n_train, spec.n_clients, rng)
                self.sampler = ClientSampler(train, shards, rng)
            else:
                shards = partition_noniid_shards(
                    shard_labels, spec.n_clients, rng)
                self.sampler = ClientSampler(train, shards, rng)
            self.sfl = spec.resolved_sfl
            n_slots = spec.n_clients
            self._plane = None
        else:
            # streaming traffic (DESIGN.md §14): the simulator is built
            # at pow2 slot capacity with every slot bound to the dummy
            # pool; the plane admits the initial cohort (and every later
            # arrival's derived shard/profile) by slot surgery, so the
            # static partition is skipped entirely
            from repro.traffic import TrafficPlane, dummy_pool

            n_slots = pow2_bucket(spec.n_clients)
            self.sampler = ClientSampler(
                train, [dummy_pool() for _ in range(n_slots)], rng)
            self.sfl = dataclasses.replace(
                spec.resolved_sfl, n_devices=n_slots)
            self._plane = TrafficPlane(
                spec.traffic, n_train=spec.n_train,
                cohort=spec.n_clients, capacity=n_slots)
        # token archs: the latency/controller profile must price the
        # sequence length the cell actually trains on (CNNs ignore it)
        self.profile = model_profile(self.cfg, seq_len=spec.seq_len)
        self.devices = sample_devices(n_slots, rng)
        self.sim = SFLEdgeSimulator(
            self.model,
            self.sampler,
            test,
            self.devices,
            self.sfl,
            self.profile,
            seed=spec.seed,
            engine=spec.resolved_engine,
            conv_impl=spec.conv_impl,
            update_impl=spec.update_impl,
            fault_mode=spec.fault_mode,
            deadline_factor=spec.deadline_factor,
            mesh=spec.mesh,
            cohort_bank=self._bank,
        )
        if spec.scenario is not None:
            from repro.scenarios import make_scenario

            self.scenario = make_scenario(
                spec.scenario, self.devices, seed=spec.scenario_seed
            )
        else:
            self.scenario = None
        self.policy = policy_registry.make_policy(
            spec.policy,
            self.profile,
            self.sfl,
            estimate=spec.estimate,
            seed=spec.seed,
        )
        self._opt: Optional[HASFLOptimizer] = None
        self._ran = False
        self._resume: Optional[dict] = None

    def _build_data(self, spec: ExperimentSpec):
        """(train arrays, test batch, labels for non-IID sharding)."""
        if self.cfg.is_cnn:
            (xtr, ytr), (xte, yte) = make_cifar_like(
                self.cfg.n_classes,
                spec.n_train,
                spec.n_test,
                self.cfg.image_size,
                seed=spec.seed,
            )
            train = {"images": xtr, "labels": ytr}
            test = {"images": xte, "labels": yte}
            return train, test, ytr
        if spec.partition != "iid":
            raise ValueError(
                "token architectures use synthetic LM data with no class "
                "labels; only partition='iid' is supported"
            )
        if self.cfg.is_enc_dec:
            return self._asr_data(spec)
        tokens, labels = make_lm_data(
            self.cfg.vocab_size,
            spec.n_train + spec.n_test,
            spec.seq_len,
            seed=spec.seed,
        )
        train = {
            "tokens": tokens[: spec.n_train],
            "labels": labels[: spec.n_train],
        }
        test = {
            "tokens": tokens[spec.n_train :],
            "labels": labels[spec.n_train :],
        }
        return train, test, None

    def _asr_data(self, spec: ExperimentSpec):
        """Audio cells: 30-s utterances as log-mel features (two frames
        per encoder position) and transcripts of ``seq_len`` tokens."""
        n = spec.n_train
        feats, seqs = make_asr_data(
            n + spec.n_test, 2 * self.cfg.encoder_seq, self.cfg.n_mels,
            self.cfg.vocab_size, spec.seq_len, seed=spec.seed)
        train = {"features": feats[:n], "tokens": seqs[:n, :-1],
                 "labels": seqs[:n, 1:]}
        test = {"features": feats[n:], "tokens": seqs[n:, :-1],
                "labels": seqs[n:, 1:]}
        return train, test, None

    # -- conveniences -------------------------------------------------------

    @property
    def engine(self) -> str:
        return self.sim.engine

    @property
    def plane(self):
        """The cell's `TrafficPlane` (None on synchronous specs) — the
        event log and slot state live here after `run()`."""
        return self._plane

    @property
    def optimizer(self) -> HASFLOptimizer:
        """The cell's joint BS/MS optimizer (built on first use).

        Figure drivers that run `repro.core.baselines.policy` directly
        use this instead of wiring their own `HASFLOptimizer`.
        """
        if self._opt is None:
            self._opt = HASFLOptimizer(self.profile, self.devices, self.sfl)
        return self._opt

    def grid_key(self):
        return self.spec.grid_key()

    def _consume(self) -> None:
        if self._ran:
            raise RuntimeError(
                "Session already ran; sessions are single-shot — build a "
                "fresh Session from the spec to rerun"
            )
        self._ran = True

    # -- crash-safe snapshots (DESIGN.md §12) --------------------------------

    def _snapshot_cb(self, t: int, clock: float, b, cuts, res: SimResult):
        """Write the full run state at round ``t`` (atomic, tmp-then-
        rename — `training.checkpoint.save_snapshot`).

        Everything the resumed loop touches is captured: the stacked
        parameters, the decision in force, the metric/decision history,
        the two host RNG streams (sampling and policy), and the
        controller's cross-boundary state.  The scenario is *not*
        snapshotted — it regenerates its trace deterministically from
        ``spec.scenario_seed``.
        """
        leaves, treedef = jax.tree_util.tree_flatten(self.sim._stacked)
        arrays = {f"param_leaf_{i}": np.asarray(x)
                  for i, x in enumerate(leaves)}
        arrays.update(
            b=np.asarray(b),
            cuts=np.asarray(cuts),
            res_rounds=np.asarray(res.rounds, np.int64),
            res_clock=np.asarray(res.clock, np.float64),
            res_train_loss=np.asarray(res.train_loss, np.float64),
            res_test_loss=np.asarray(res.test_loss, np.float64),
            res_test_acc=np.asarray(res.test_acc, np.float64),
            res_b_history=np.asarray(res.b_history),
            res_cut_history=np.asarray(res.cut_history),
        )
        meta = {
            "clock": float(clock),
            "treedef": str(treedef),
            "n_param_leaves": len(leaves),
            "rng_sampler": self.sampler.rng.bit_generator.state,
            "rng_sim": self.sim.rng.bit_generator.state,
            "spec": self.spec.to_dict(),
        }
        state_fn = getattr(self.policy, "state_dict", None)
        if state_fn is not None:
            meta["controller"] = state_fn()
        if self._plane is not None:
            # traffic cells (DESIGN.md §14): fold the plane's host state
            # — slot sessions, event heap, pool bindings, population
            # cursor — into the same snapshot, so `resume` replays the
            # event walk bitwise from the boundary
            tr_arrays, tr_meta = self._plane.state(self.sim.store)
            arrays.update(tr_arrays)
            meta["traffic"] = tr_meta
        ckpt.save_snapshot(self.spec.checkpoint_dir, t, arrays, meta)

    def _restore_state(self, arrays: dict, meta: dict) -> None:
        """Load a snapshot back onto this (freshly built) session."""
        leaves, treedef = jax.tree_util.tree_flatten(self.sim._stacked)
        if meta["n_param_leaves"] != len(leaves) or \
                meta["treedef"] != str(treedef):
            raise ValueError(
                "snapshot parameter tree does not match the spec's model "
                f"({meta['n_param_leaves']} leaves vs {len(leaves)})")
        new_leaves = [
            jnp.asarray(ckpt.as_leaf_dtype(arrays[f"param_leaf_{i}"],
                                           np.asarray(l).dtype))
            for i, l in enumerate(leaves)
        ]
        self.sim._stacked = jax.tree_util.tree_unflatten(treedef, new_leaves)
        self.sampler.rng.bit_generator.state = meta["rng_sampler"]
        self.sim.rng.bit_generator.state = meta["rng_sim"]
        if "controller" in meta:
            self.policy.load_state_dict(meta["controller"])
        if self._plane is not None:
            self._plane.restore(self.sim, arrays, meta["traffic"])
        res = SimResult(
            rounds=[int(x) for x in arrays["res_rounds"]],
            clock=[float(x) for x in arrays["res_clock"]],
            train_loss=[float(x) for x in arrays["res_train_loss"]],
            test_loss=[float(x) for x in arrays["res_test_loss"]],
            test_acc=[float(x) for x in arrays["res_test_acc"]],
            b_history=[np.asarray(r) for r in arrays["res_b_history"]],
            cut_history=[np.asarray(r) for r in arrays["res_cut_history"]],
        )
        self._resume = {
            "t": int(meta["step"]),
            "clock": float(meta["clock"]),
            "b": np.asarray(arrays["b"]),
            "cuts": np.asarray(arrays["cuts"]),
            "res": res,
        }

    @classmethod
    def resume(cls, spec: ExperimentSpec, checkpoint_dir: Optional[str] = None,
               step: Optional[int] = None) -> "Session":
        """Rebuild a session from the latest (or given) snapshot under
        ``checkpoint_dir`` (default: ``spec.checkpoint_dir``); its
        `run()` then continues bitwise-identically to an uninterrupted
        run of the same spec — same decision stream, clock floats, eval
        losses, and final parameters.
        """
        spec = spec.validated()
        path = checkpoint_dir or spec.checkpoint_dir
        if path is None:
            raise ValueError("no checkpoint_dir on the spec or the call")
        arrays, meta = ckpt.load_snapshot(path, step)
        saved = dict(meta["spec"])
        # the dir itself may legitimately differ (moved snapshots); the
        # json round-trip normalizes containers so the comparison sees
        # exactly what the snapshot recorded
        saved.pop("checkpoint_dir", None)
        ours = json.loads(json.dumps(spec.to_dict()))
        ours.pop("checkpoint_dir", None)
        if saved != ours:
            raise ValueError(
                "snapshot was written by a different spec; refusing to "
                "resume (diff keys: "
                f"{sorted(k for k in ours if saved.get(k) != ours[k])})")
        sess = cls(spec)
        sess._restore_state(arrays, meta)
        return sess

    # -- execution ----------------------------------------------------------

    def run(self, *, verbose: bool = False) -> SimResult:
        """Run this cell alone (any engine)."""
        self._consume()
        snapshot_cb = self._snapshot_cb if self.spec.checkpoint_every else None
        return self.sim.run(
            self.policy,
            rounds=self.spec.rounds,
            eval_every=self.spec.eval_every,
            reconfigure_every=self.spec.reconfigure_every,
            verbose=verbose,
            scenario=self.scenario,
            checkpoint_every=self.spec.checkpoint_every,
            snapshot_cb=snapshot_cb,
            resume=self._resume,
            traffic=self._plane,
        )

    @classmethod
    def run_grid(
        cls,
        specs: Sequence[Union[ExperimentSpec, "Session"]],
        *,
        runner: Optional[str] = None,
        verbose: bool = False,
    ) -> List[SimResult]:
        """Run a grid of cells, batching compatible ones (DESIGN.md §10).

        Cells sharing `ExperimentSpec.grid_key()` — same model, data,
        seed, `SFLConfig`, and round segmentation; policy and scenario
        free — are stacked on a leading grid axis and executed as one
        vmapped mega-run over the scan engine's donated carry.
        Incompatible or non-scan cells fall back to sequential
        `run()`.  Results come back in input order and are bitwise
        identical to running each spec alone.

        ``runner``: ``None``/``"grid"`` batches every compatible group
        (the historical behavior); ``"sequential"`` forces per-cell
        `run()`; ``"auto"`` consults the `repro.api.runners` registry
        per group — it fills unset kernel impls (specs only; already
        built Sessions are rejected, their simulators are pinned) and
        picks grid vs sequential per arch family x backend
        (DESIGN.md §11).
        """
        if runner not in (None, "grid", "sequential", "auto"):
            raise ValueError(f"unknown runner {runner!r}")
        if runner == "auto":
            from repro.api import runners as R

            if any(isinstance(s, Session) for s in specs):
                raise ValueError(
                    "runner='auto' needs ExperimentSpecs (a built "
                    "Session's kernel impls are already pinned)"
                )
            specs = [R.apply_choice(s) for s in specs]
        sessions = [s if isinstance(s, Session) else cls(s) for s in specs]
        results: List[Optional[SimResult]] = [None] * len(sessions)
        for idxs in group_cells([sessions[i].spec for i in range(len(sessions))]):
            members = [sessions[i] for i in idxs]
            sequential = (
                len(members) == 1
                or runner == "sequential"
                or (
                    runner == "auto"
                    and R.pick(members[0].spec).runner == "sequential"
                )
            )
            if sequential:
                for i, sess in zip(idxs, members):
                    results[i] = sess.run(verbose=verbose)
                continue
            for sess in members:
                sess._consume()
            for i, r in zip(idxs, run_group(members, verbose=verbose)):
                results[i] = r
        return results


def run_grid(
    specs: Sequence[Union[ExperimentSpec, Session]],
    *,
    runner: Optional[str] = None,
    verbose: bool = False,
) -> List[SimResult]:
    """Module-level alias for `Session.run_grid`."""
    return Session.run_grid(specs, runner=runner, verbose=verbose)
