"""Running one cell of the benchmark: set-up, window, trace, correctness.

The system under test is the program's own entry, `repro.api.Session`
(`Session.run`, or `Session.run_grid(runner="auto")`), run as one
closed-loop experiment whose round count lies beyond any window.  The
harness only wraps methods of the built simulator, from this file:

- ``sim._scan_fn`` (the segment executable): its dispatch is timed, its
  inputs and per-round losses are kept for the rounds that are checked;
- ``sim._record_metrics`` (eval and the one host fetch per segment): a
  segment boundary ends when it returns, which is where the window
  starts, is measured, and stops (by raising `StopWindow`);
- ``sim._advance_clock`` (the simulated clock), ``sim._maybe_reconfigure``
  (the controller),
  ``sim._segment_participation``, ``sim._unit_cuts``,
  ``sim.store.segment_indices`` and ``sim.store.row_mask``: host time of
  the control loop between segments.

Set-up builds the `Session`, makes the weights and data from the seed,
and runs the first ``setup_rounds`` rounds (two reconfiguration
periods), which compiles or loads every executable the window uses.
The checked rounds are the first ``check.rounds`` of set-up: the
reference replays them after the window, when the program's state is
freed.
"""
from __future__ import annotations

import contextlib
import gc
import math
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import cells
from chipbench import compare as CMP
from chipbench import program_trace as PT
from chipbench import trace as TR

TRACE_DIR = os.path.join(cells.BENCH_DIR, ".out", "trace")


class StopWindow(Exception):
    """Raised at the first segment boundary past the window's end."""


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Inputs from the traffic file and the seed
# ---------------------------------------------------------------------------

def make_fleet(traffic: Dict, seed: int) -> List[Dict]:
    """The Table-I fleet: one fixed draw from ``fleet_seed``, handed out to
    the clients in an order drawn from the run's seed, so every seed runs
    the same set of devices (and the controller the same set of
    decisions) in another order.  Plain records, which the program gets
    as its `DeviceProfile`s and the reference as they are."""
    f = traffic["fleet"]
    rng = np.random.default_rng(f["fleet_seed"])
    devs = []
    for _ in range(f["n"]):
        devs.append(dict(
            flops=float(rng.uniform(*f["flops"])),
            up_bw=float(rng.uniform(*f["up_bw"])),
            down_bw=float(rng.uniform(*f["down_bw"])),
            fed_up_bw=float(rng.uniform(*f["up_bw"])),
            fed_down_bw=float(rng.uniform(*f["down_bw"])),
            memory=float(f["memory_bits"]),
        ))
    order = np.random.default_rng(seed).permutation(f["n"])
    return [devs[i] for i in order]


def build_spec(cfg: Dict, traffic: Dict, seed: int):
    from repro.api import ExperimentSpec
    from repro.config import SFLConfig
    from repro.mesh import MeshSpec

    mesh = traffic.get("mesh")
    ctl = traffic["controller"]
    # a token family's sequence length; a CNN's mix has none
    extra = {"seq_len": traffic["seq_len"]} if "seq_len" in traffic else {}
    return ExperimentSpec(
        arch=cfg["arch"], n_clients=traffic["fleet"]["n"],
        partition=traffic["partition"], n_train=traffic["n_train"],
        n_test=traffic["n_test"], seed=seed, policy=traffic["policy"],
        estimate=traffic["estimate"], scenario=traffic["scenario"],
        rounds=10 ** 7, eval_every=traffic["eval_every"],
        reconfigure_every=traffic["reconfigure_every"], engine="scan",
        fault_mode=traffic["fault_mode"],
        mesh=None if mesh is None else MeshSpec(**mesh),
        sfl=SFLConfig(agg_interval=traffic["agg_interval"],
                      lr=traffic["lr"], clip_norm=traffic["clip_norm"],
                      **{k: ctl[k] for k in SFL_KEYS}),
        **extra,
    )


# the controller constants the program's SFLConfig takes from the traffic
SFL_KEYS = ("server_flops", "server_fed_bw", "epsilon", "beta", "theta_gap",
            "optimizer_state_mult", "max_batch")


def leaf_name(path) -> str:
    """``[3]['proj']['w']`` -> ``3.proj.w`` (the reference's names)."""
    parts = []
    for k in path:
        parts.append(str(getattr(k, "idx", getattr(k, "key", k))))
    return ".".join(parts)


# ---------------------------------------------------------------------------
# Planted faults (tests and calibration only; never in a benchmark run)
# ---------------------------------------------------------------------------

FAULTS = ("frozen", "half_batch", "decision", "clock")


def _plant(fault: Optional[str], session) -> None:
    """Break the run underneath: ``frozen`` returns the state unchanged,
    ``half_batch`` leaves out the second half of each client's rows,
    ``decision`` puts the first decision's cuts one layer lower (an
    off-by-one where the decision is made), ``clock`` walks each segment
    one round short."""
    import jax
    import jax.numpy as jnp

    if fault is None:
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    sim = session.sim
    if fault == "decision":
        policy, calls = session.policy, []

        def first_cut_lower(s, rng):
            b, cuts = policy(s, rng)
            calls.append(1)
            if len(calls) == 1:
                cuts = np.maximum(np.asarray(cuts) - 1, 1)
            return b, cuts

        session.policy = first_cut_lower
        return
    if fault == "clock":
        walk = sim._advance_clock
        sim._advance_clock = lambda clock, t, nxt, *a, **kw: walk(
            clock, t, nxt - 1, *a, **kw)
        return
    inner = sim._scan_fn
    if fault == "frozen":
        def scan(stacked, *rest):
            keep = jax.tree_util.tree_map(jnp.copy, stacked)
            _, losses = inner(stacked, *rest)
            return keep, losses
    elif fault == "half_batch":
        def scan(stacked, t0, idx, row_mask, *rest):
            rm = np.asarray(row_mask)
            keep = np.ceil(rm.sum(axis=1, keepdims=True) / 2)
            rm = rm * (np.arange(rm.shape[1])[None, :] < keep)
            return inner(stacked, t0, idx, rm.astype(np.float32), *rest)
    sim._scan_fn = scan


# ---------------------------------------------------------------------------
# The probe: wraps the simulator, drives the window
# ---------------------------------------------------------------------------

class Probe:
    def __init__(self, cell: Dict, seconds: float, tracing: bool,
                 fault: Optional[str] = None):
        self.traffic = cell["traffic"]
        self.seconds = seconds
        self.tracing = tracing
        self.fault = fault
        chk = self.traffic["check"]
        self.check_rounds = int(chk["rounds"])
        self.delta_at = [int(r) for r in chk["delta_at"]]
        self.setup_rounds = int(self.traffic["setup_rounds"])
        self.trace_rounds = int(self.traffic["trace_rounds"])
        self.sim = None
        self.fleet: List[Dict] = []
        self.session_s = None
        # checked rounds: program readings and the inputs they used
        self.init: Optional[Dict[str, np.ndarray]] = None
        self.idx: List[np.ndarray] = []
        self.losses: Dict[int, np.ndarray] = {}
        self.evals: Dict[int, float] = {}
        self.deltas: Dict[int, Dict[str, float]] = {}
        self._pending_losses = None
        self._delta_fn = None
        self._t = 0                           # round of the last boundary
        # window
        self.t_win0 = None
        self.in_window = False
        self.boundaries: List[tuple] = []     # (round, host clock)
        self.host_s: List[float] = []         # host self time per boundary
        self._host_acc = 0.0
        self.compiles = {"setup": 0, "window": 0}
        self.nonfinite = 0
        self.decisions: List[tuple] = []
        self.clocks: List[tuple] = []         # (round, simulated clock)

    # -- spans and host time ------------------------------------------------
    def _span(self, name: str):
        if self.tracing:
            import jax

            return jax.profiler.TraceAnnotation(TR.SPAN_PREFIX + name)
        return contextlib.nullcontext()

    def _host(self, name: str, fn):
        def wrapped(*a, **kw):
            t = time.perf_counter()
            with self._span(name):
                out = fn(*a, **kw)
            if self.in_window:
                self._host_acc += time.perf_counter() - t
            return out
        return wrapped

    def on_compile(self) -> None:
        self.compiles["window" if self.in_window else "setup"] += 1

    # -- instrumenting a built simulator -----------------------------------
    def attach(self, session) -> None:
        sim = self.sim = session.sim
        _plant(self.fault, session)
        inner_scan = sim._scan_fn

        def scan(stacked, t0, idx, *rest):
            t = self._t
            if t == 0:
                self._keep_init(stacked)
            if t < self.check_rounds:
                self.idx.append(np.asarray(idx))
            out = self._host("dispatch", inner_scan)(stacked, t0, idx, *rest)
            self._pending_losses = out[1] if t < self.check_rounds else None
            return out

        sim._scan_fn = scan
        record = sim._record_metrics

        def record_metrics(res, t, *a, **kw):
            with self._span("eval_fetch"):
                record(res, t, *a, **kw)
            self.boundary(res, t)

        sim._record_metrics = record_metrics
        for name in ("_advance_clock", "_maybe_reconfigure",
                     "_segment_participation", "_unit_cuts"):
            setattr(sim, name, self._host(name.strip("_"), getattr(sim, name)))
        for name in ("segment_indices", "row_mask"):
            setattr(sim.store, name,
                    self._host(name, getattr(sim.store, name)))

    def _keep_init(self, stacked) -> None:
        import jax

        flat, _ = jax.tree_util.tree_flatten_with_path(stacked)
        self.init = {leaf_name(p): np.asarray(x[0]) for p, x in flat}

    def _delta_norms(self, stacked) -> Dict[str, float]:
        """Norm of each stacked leaf's change from the initial weights."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        flat, _ = jax.tree_util.tree_flatten_with_path(stacked)
        names = [leaf_name(p) for p, _ in flat]
        leaves = [x for _, x in flat]
        sh = leaves[0].sharding
        if isinstance(sh, NamedSharding):
            place = NamedSharding(sh.mesh, PartitionSpec())
        else:
            place = next(iter(sh.device_set))
        x0 = [jax.device_put(self.init[n], place) for n in names]
        if self._delta_fn is None:
            self._delta_fn = jax.jit(lambda xs, x0s: [
                jnp.sqrt(jnp.sum(jnp.square(x - a[None])))
                for x, a in zip(xs, x0s)])
        out = self._delta_fn(leaves, x0)
        return {n: float(v) for n, v in zip(names, out)}

    # -- a segment boundary has ended -----------------------------------------
    def boundary(self, res, t: int) -> None:
        import jax

        now = time.perf_counter()
        self._t = t
        if not all(math.isfinite(x) for x in
                   (res.train_loss[-1], res.test_loss[-1])):
            self.nonfinite += 1
        self.clocks.append((t, float(res.clock[-1])))
        if len(res.b_history) > len(self.decisions):
            for b, c in zip(res.b_history[len(self.decisions):],
                            res.cut_history[len(self.decisions):]):
                self.decisions.append((np.asarray(b), np.asarray(c)))
        if t <= self.check_rounds:
            seg = np.asarray(self._pending_losses)
            for r in range(seg.shape[0]):
                self.losses[t - seg.shape[0] + r + 1] = seg[r]
            self.evals[t] = float(res.test_loss[-1])
            if t in self.delta_at:
                self.deltas[t] = self._delta_norms(self.sim._stacked)
        if t == self.setup_rounds:
            if self.tracing:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # host spans, no call tree
                jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
                with self._span("window_start"):
                    pass
            self.t_win0 = time.perf_counter()
            self.boundaries.append((t, self.t_win0))
            self.in_window = True
            self._host_acc = 0.0
            return
        if not self.in_window:
            return
        self.host_s.append(self._host_acc)
        self._host_acc = 0.0
        self.boundaries.append((t, now))
        done = (t - self.setup_rounds >= self.trace_rounds if self.tracing
                else now - self.t_win0 >= self.seconds)
        if done:
            self.in_window = False
            if self.tracing:
                with self._span("window_end"):
                    pass
                jax.profiler.stop_trace()
            raise StopWindow

    # -- what the window measured ---------------------------------------------
    def window(self) -> Dict:
        (t0, c0), (t1, c1) = self.boundaries[0], self.boundaries[-1]
        seg_ms = []
        for (ta, ca), (tb, cb) in zip(self.boundaries, self.boundaries[1:]):
            seg_ms.append(1e3 * (cb - ca) / (tb - ta))
        return {"rounds": t1 - t0, "wall_s": c1 - c0,
                "segments": len(seg_ms), "segment_ms_per_round": seg_ms}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

class Context:
    """What a metric reader may read (see ``metrics/<name>.py``): the
    cell, the window, host times, and in a traced run ``trace`` and
    ``summary`` (`chipbench.trace`) and ``program``, the same trace by
    the program's spans and scopes (`chipbench.program_trace`); those
    three are None in an untraced run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _session_class(probe: Probe, fleet: List[Dict]):
    from repro.api import Session
    from repro.config import DeviceProfile

    class ProbedSession(Session):
        def __init__(self, spec):
            t = time.perf_counter()
            super().__init__(spec)
            self.devices = [DeviceProfile(**d) for d in fleet]
            self.sim.set_devices(self.devices)
            probe.session_s = time.perf_counter() - t
            probe.attach(self)

    return ProbedSession


def _run_program(probe: Probe, spec, entry: str, fleet: List[Dict]) -> None:
    """Run the program's entry until the probe stops the window."""
    cls = _session_class(probe, fleet)
    try:
        if entry == "Session.run":
            cls(spec).run()
        elif entry == "Session.run_grid(runner=auto)":
            cls.run_grid([spec], runner="auto")
        else:
            raise ValueError(f"unknown entry {entry!r}")
    except StopWindow:
        pass
    else:
        raise RuntimeError("the run ended before its window")


def peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


_CURRENT: List[Optional[Probe]] = [None]


def _on_compile_event(name, secs, **kw) -> None:
    if name == "/jax/core/compile/jaxpr_to_mlir_module_duration" \
            and _CURRENT[0] is not None:
        _CURRENT[0].on_compile()


def run_program(cell: Dict, seed: int, seconds: float, tracing: bool,
                fault: Optional[str] = None, *,
                setup_rounds: Optional[int] = None) -> Probe:
    """Build the cell's Session, run set-up and the window; return the
    probe with everything it recorded."""
    from jax import monitoring

    if not getattr(_on_compile_event, "registered", False):
        monitoring.register_event_duration_secs_listener(_on_compile_event)
        _on_compile_event.registered = True
    cfg, traffic = cell["config"], cell["traffic"]
    probe = Probe(cell, seconds, tracing, fault)
    if setup_rounds is not None:
        probe.setup_rounds = setup_rounds
    _CURRENT[0] = probe
    fleet = probe.fleet = make_fleet(traffic, seed)
    spec = build_spec(cfg, traffic, seed)
    try:
        _run_program(probe, spec, traffic["entry"], fleet)
    finally:
        _CURRENT[0] = None
    return probe


def readings(probe: Probe) -> Dict:
    """The program's readings (the checked rounds' losses, evals and
    changes; every decision and every simulated clock it reported) and
    the inputs the reference needs to replay them: the initial weights,
    to compare with its own, and the gather plan of the checked rounds,
    which says which samples each client drew."""
    b, cuts = probe.decisions[0]
    return {"init": probe.init, "idx": probe.idx, "b": b, "cuts": cuts,
            "losses": probe.losses, "evals": probe.evals,
            "deltas": probe.deltas, "decisions": probe.decisions,
            "clocks": probe.clocks, "fleet": probe.fleet}


def run_cell(cell: Dict, seed: int, seconds: float, tracing: bool, *,
             t_start: float, devices, keep_trace: Optional[str] = None) -> Dict:
    """Set up, measure, check.  Returns the result line as a dict.
    ``keep_trace`` copies the traced run's profile there."""
    cfg, traffic, w = cell["config"], cell["traffic"], cell["workload"]
    probe = run_program(cell, seed, seconds, tracing)
    setup_s = probe.t_win0 - t_start
    peak = peak_bytes(devices[:w["chips"]])
    say(f"memory_stats: {devices[0].memory_stats()}")
    win = probe.window()
    b, cuts = probe.decisions[0]
    changed = sum(1 for bb, cc in probe.decisions[1:]
                  if not (np.array_equal(bb, b) and np.array_equal(cc, cuts)))
    say(f"window: {win['rounds']} rounds in {win['segments']} segments, "
        f"{win['wall_s']:.3f} s; compiles in set-up {probe.compiles['setup']},"
        f" in window {probe.compiles['window']}")
    say("segments, ms per round: " + " ".join(
        f"{x:.2f}" for x in win["segment_ms_per_round"]))
    say("host ms per boundary: " + " ".join(
        f"{1e3 * x:.2f}" for x in probe.host_s))
    say(f"decisions: {len(probe.decisions)} made, {changed} differ from the "
        f"first; b {int(b.min())}-{int(b.max())} (sum {int(b.sum())}), "
        f"b_pad {1 << max(0, int(b.max()) - 1).bit_length()}, "
        f"cuts {sorted(set(cuts.tolist()))}")
    tr = summ = prog_tr = None
    if tracing:
        if keep_trace:
            shutil.copytree(TRACE_DIR, keep_trace, dirs_exist_ok=True)
        events = TR.events(TRACE_DIR)
        tr = TR.parse(events)
        summ = TR.summary(tr)
        prog_tr = PT.parse(events, tr.window())
        del events
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    # free the program's state before the reference runs
    program = readings(probe)
    probe.sim = None
    gc.collect()
    kind = devices[0].device_kind
    ctx = Context(
        cell=cell, cfg=cfg, traffic=traffic, workload=w["name"],
        window=win, setup_s=setup_s, session_s=probe.session_s,
        peak_bytes=peak, chips=w["chips"], trace=tr, summary=summ,
        b=b, cuts=cuts, host_s=probe.host_s,
        program=prog_tr,
        peaks=cells.peaks(kind) if devices[0].platform == "tpu" else None)
    kind_metrics = cell["per_layer"] if tracing else cell["end_to_end"]
    metrics = {}
    for m in kind_metrics:
        v = cells.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks, others = CMP.check(cell, seed, program)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, v in others.items():
        print(f"reading {name} {v!r} (not compared)", file=sys.stderr,
              flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": win["rounds"],
           "failed": probe.nonfinite, "metrics": metrics, "device": device}
    if summ is not None:
        device["busy_s"] = summ["busy_s"]
        device["window_s"] = summ["window_s"]
        out["breakdown"] = {"device_ops": summ["device_ops"],
                            "idle_gaps": summ["idle_gaps"]}
    out["checks"] = checks
    return out
