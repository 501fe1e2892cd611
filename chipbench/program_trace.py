"""Reduction of a profiler trace by the program's own spans and scopes.

The program records host spans named ``repro.<step>`` around the steps
of its segment loop (`repro.utils.trace`), with the segment's first
round ``t`` and counters as args, and names its device steps with
`jax.named_scope`: ``gather``, ``client_grads`` and ``update`` in the
scan segment, ``im2col`` around the patch layout of the kernel conv.
On a TPU each op event of the ``XLA Ops`` line carries its ``op_name``
path in its ``tf_op`` arg
(``jit(_scan_segment)/while/body/closed_call/client_grads/...:``); a
fusion carries the path of the op XLA names it after, and the layout
copies XLA adds carry none.  The ``XLA Modules`` line holds one event
per executable run, named after its module.

`parse` keeps what `chipbench.trace.parse` leaves out: the ``repro.*``
spans with their args, each device op's path, and the executable runs.
Everything after it works on plain records, so it can be checked on a
synthetic trace:

- ``ScopedOp``: one device op with its ``op_name`` path;
- ``Run``: one executable run (module name, run id, interval);
- ``ProgramSpan``: one ``repro.*`` host span with its args.

`report` gives the readings a traced window holds: host time per
segment by step, idle outside and inside executable runs, device time
per round under each scope, row use, and the checks that every gap and
op has an owner.
"""
from __future__ import annotations

from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from chipbench import trace as TR

PREFIX = "repro."
SEGMENT = PREFIX + "segment"
DISPATCH = PREFIX + "dispatch"
# children of a segment that wait on the device; every other child is
# host work
WAITS = (PREFIX + "fetch", PREFIX + "eval_fetch")
# the scan segment's device steps, and the kernel conv's patch layout
UPDATE = "update"
SEGMENT_SCOPES = ("gather", "client_grads", UPDATE)
IM2COL = "im2col"
SCAN_MODULE = "jit__scan_segment("
Interval = Tuple[float, float]


@dataclass(frozen=True)
class ScopedOp:
    name: str
    start: float
    end: float
    category: str = ""
    path: str = ""

    def under(self, scope: str) -> bool:
        """Whether ``scope`` names one of the op's enclosing scopes (the
        last part of the path is the op itself, not a scope)."""
        return scope in self.path.split("/")[:-1]


@dataclass(frozen=True)
class Run:
    module: str
    run_id: str
    start: float
    end: float


@dataclass(frozen=True)
class ProgramSpan:
    name: str
    start: float
    end: float
    args: Dict[str, str] = field(default_factory=dict, compare=False)

    def arg(self, key: str) -> Optional[int]:
        v = self.args.get(key)
        return None if v is None else int(v)


@dataclass
class ProgramTrace:
    """Device ops and executable runs per chip, the ``repro.*`` spans,
    and the window of `chipbench.trace`."""
    ops: Dict[str, List[ScopedOp]]
    runs: Dict[str, List[Run]]
    spans: List[ProgramSpan]
    window: Interval


def op_path(args: Dict) -> str:
    """The op's ``op_name`` path from its event's ``tf_op`` arg, which
    reads ``<op_name>:<op type>`` with the type left empty; empty where
    the op has none."""
    return str(args.get("tf_op", "")).rsplit(":", 1)[0]


def parse(events: List[Dict], window: Interval) -> ProgramTrace:
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e.get("tid"))] = e["args"]["name"]
    ops: Dict[str, List[ScopedOp]] = defaultdict(list)
    runs: Dict[str, List[Run]] = defaultdict(list)
    spans: List[ProgramSpan] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e.get("pid"), "")
        t0 = float(e["ts"]) * 1e-6
        t1 = t0 + float(e.get("dur", 0.0)) * 1e-6
        args = e.get("args", {})
        if proc.startswith("/device:") and "CPU" not in proc:
            line = threads.get((e["pid"], e.get("tid")))
            if line == "XLA Ops":
                ops[proc].append(ScopedOp(e["name"], t0, t1,
                                          args.get("hlo_category", ""),
                                          op_path(args)))
            elif line == "XLA Modules":
                runs[proc].append(Run(e["name"], str(args.get("run_id", "")),
                                      t0, t1))
        elif proc.startswith("/host:") and e["name"].startswith(PREFIX):
            spans.append(ProgramSpan(e["name"], t0, t1,
                                     {k: str(v) for k, v in args.items()}))
    for rs in runs.values():
        rs.sort(key=lambda r: r.start)
    return ProgramTrace(dict(ops), dict(runs), spans, window)


def load(trace_dir: str) -> ProgramTrace:
    """Read the newest ``*.trace.json.gz`` under ``trace_dir``; the
    window is `chipbench.trace`'s."""
    events = TR.events(trace_dir)
    return parse(events, TR.parse(events).window())


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def work(ops: Iterable[ScopedOp]) -> List[ScopedOp]:
    """Ops that are work of their own (control flow left out, as in
    `chipbench.trace.top_ops`)."""
    return [o for o in ops if o.category not in TR.CONTROL_FLOW]


def in_run(ops: List[ScopedOp], run: "Run") -> List[ScopedOp]:
    """The ops that start inside the run."""
    return [o for o in ops if run.start <= o.start < run.end]


# ---------------------------------------------------------------------------
# Device: idle outside and inside executable runs, time under scopes
# ---------------------------------------------------------------------------

def idle_split(pt: ProgramTrace, chip: str) -> Tuple[List[Interval],
                                                       List[Interval]]:
    """The window's idle intervals on ``chip``, split into those outside
    every executable run and those inside one (between its ops).  Busy
    is `chipbench.trace`'s: the union of every op, control flow too."""
    lo, hi = pt.window
    idle = TR.gaps(TR.busy(pt.ops.get(chip, []), lo, hi), lo, hi)
    in_runs = TR.union(TR.clip(((r.start, r.end)
                                for r in pt.runs.get(chip, [])), lo, hi))
    return (intersect(idle, TR.gaps(in_runs, lo, hi)),
            intersect(idle, in_runs))


def idle_shares(pt: ProgramTrace) -> Optional[Tuple[float, float]]:
    """(outside runs, inside runs) idle as % of the window, averaged over
    chips; their sum is `device.idle_share`.  None without device ops
    or runs."""
    if not pt.ops or not pt.runs:
        return None
    lo, hi = pt.window
    outs = ins = 0.0
    for chip in pt.ops:
        outer, inner = idle_split(pt, chip)
        outs += TR.length(outer)
        ins += TR.length(inner)
    scale = 100.0 / len(pt.ops) / (hi - lo)
    return outs * scale, ins * scale


def scope_seconds(pt: ProgramTrace, scope: str) -> float:
    """Device seconds of the window's ops under ``scope``, summed over
    the chips (control flow left out)."""
    lo, hi = pt.window
    return sum(TR.length(TR.clip(((o.start, o.end) for o in work(ops)
                                  if o.under(scope)), lo, hi))
               for ops in pt.ops.values())


def scope_ms_per_round(pt: ProgramTrace, scope: str,
                       rounds: int) -> Optional[float]:
    """Device ms per round and chip of the window's ops under ``scope``;
    None where no op carries the scope (a program without it)."""
    s = scope_seconds(pt, scope)
    if s <= 0 or not rounds:
        return None
    return 1e3 * s / rounds / len(pt.ops)


def scan_runs(pt: ProgramTrace, chip: str) -> List[Run]:
    lo, hi = pt.window
    return [r for r in pt.runs.get(chip, [])
            if r.module.startswith(SCAN_MODULE) and r.start >= lo
            and r.end <= hi]


def scoped_shares(pt: ProgramTrace, chip: str) -> List[float]:
    """For each of the window's scan-segment runs, the share of its op
    time (control flow left out) under one of `SEGMENT_SCOPES`, in %."""
    ops = work(pt.ops.get(chip, []))
    out = []
    for r in scan_runs(pt, chip):
        mine = in_run(ops, r)
        total = sum(o.end - o.start for o in mine)
        scoped = sum(o.end - o.start for o in mine
                     if any(o.under(s) for s in SEGMENT_SCOPES))
        if total > 0:
            out.append(100.0 * scoped / total)
    return out


# ---------------------------------------------------------------------------
# Host: the program's steps
# ---------------------------------------------------------------------------

def window_spans(pt: ProgramTrace) -> List[ProgramSpan]:
    """The ``repro.*`` spans that lie wholly inside the window."""
    lo, hi = pt.window
    return [s for s in pt.spans if s.start >= lo and s.end <= hi]


def steps(pt: ProgramTrace) -> List[ProgramSpan]:
    """Every span but the segments'."""
    return [s for s in pt.spans if s.name != SEGMENT]


def steps_by_segment(pt: ProgramTrace) -> Dict[int, Dict[str, float]]:
    """Seconds of each step of each segment in the window, keyed by the
    segment's first round ``t`` and the step's name (without the
    prefix)."""
    out: Dict[int, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for s in window_spans(pt):
        t = s.arg("t")
        if s.name != SEGMENT and t is not None:
            out[t][s.name[len(PREFIX):]] += s.end - s.start
    return {t: dict(v) for t, v in out.items()}


def host_ms(pt: ProgramTrace) -> Optional[float]:
    """Mean host ms per segment in its steps, the waits on the device
    left out; None without the program's spans."""
    by_seg = steps_by_segment(pt)
    if not by_seg:
        return None
    waits = {w[len(PREFIX):] for w in WAITS}
    per = [sum(v for k, v in st.items() if k not in waits)
           for st in by_seg.values()]
    return 1e3 * sum(per) / len(per)


def row_use(pt: ProgramTrace) -> Optional[float]:
    """Real rows over computed rows, in %, over the window's dispatches;
    None without the program's spans."""
    rows = padded = 0
    for s in window_spans(pt):
        if s.name == DISPATCH:
            rows += s.arg("rows")
            padded += s.arg("padded_rows")
    return 100.0 * rows / padded if padded else None


def idle_owners(pt: ProgramTrace, chip: str) -> Dict[str, float]:
    """Idle seconds outside executable runs by the step whose span covers
    them (the steps of a segment do not overlap); ``segment`` for idle
    under a segment span and no step, ``none`` outside every span."""
    outer, _ = idle_split(pt, chip)
    out: Dict[str, float] = defaultdict(float)
    for s in steps(pt):
        ov = TR.length(intersect(outer, [(s.start, s.end)]))
        if ov > 0:
            out[s.name[len(PREFIX):]] += ov
    rest = intersect(outer, TR.gaps(TR.union(
        (s.start, s.end) for s in steps(pt)), *pt.window))
    segs = TR.union((s.start, s.end) for s in pt.spans if s.name == SEGMENT)
    under = TR.length(intersect(rest, segs))
    for key, v in (("segment", under), ("none", TR.length(rest) - under)):
        if v > 1e-12:
            out[key] += v
    return dict(out)


def dispatch_leads(pt: ProgramTrace, chip: str) -> Optional[List[float]]:
    """For each of the window's dispatch spans, paired in order with the
    window's scan-segment runs, the seconds from the span's start to the
    run's first op; None where they do not pair up."""
    runs = scan_runs(pt, chip)
    disp = sorted((s for s in window_spans(pt) if s.name == DISPATCH),
                  key=lambda s: s.start)
    if not runs or len(runs) != len(disp):
        return None
    ops = pt.ops.get(chip, [])
    return [min(o.start for o in in_run(ops, r)) - d.start
            for r, d in zip(runs, disp)]


def report(pt: ProgramTrace, rounds: int) -> Dict:
    """Everything the window says about the program's steps and scopes
    (the first chip for the per-chip figures)."""
    shares = idle_shares(pt)
    by_seg = steps_by_segment(pt)
    per_step: Dict[str, float] = defaultdict(float)
    for v in by_seg.values():
        for k, x in v.items():
            per_step[k] += x / len(by_seg)
    out = {
        "segments": len(by_seg),
        "loop.host_ms": host_ms(pt),
        "loop.idle_share": None if shares is None else shares[0],
        "segment.idle_share": None if shares is None else shares[1],
        "segment.grads_ms": scope_ms_per_round(pt, "client_grads", rounds),
        "segment.update_ms": scope_ms_per_round(pt, UPDATE, rounds),
        "segment.gather_ms": scope_ms_per_round(pt, "gather", rounds),
        "conv.im2col_ms": scope_ms_per_round(pt, IM2COL, rounds),
        "segment.row_use": row_use(pt),
        "step_ms_per_segment": {k: 1e3 * v for k, v in sorted(
            per_step.items())},
    }
    if pt.ops:
        chip = sorted(pt.ops)[0]
        outer, _ = idle_split(pt, chip)
        scoped = scoped_shares(pt, chip)
        leads = dispatch_leads(pt, chip)
        lo, hi = pt.window
        owners = idle_owners(pt, chip)
        total = TR.length(outer)
        out.update({
            "idle_outside_runs_s": total,
            "idle_owners_s": owners,
            "idle_owned_pct": 100.0 * (1 - owners.get("none", 0.0) / total)
            if total > 0 else None,
            "scoped_share_min_pct": min(scoped) if scoped else None,
            "dispatch_lead_min_ms": None if leads is None
            else 1e3 * min(leads),
            "runs_by_module": dict(sorted(Counter(
                r.module for r in pt.runs.get(chip, [])
                if r.start >= lo and r.end <= hi).items())),
        })
    return out
