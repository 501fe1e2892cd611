"""Reduction of a profiler trace to device busy time, idle gaps,
and per-kernel time.

`load` reads the ``*.trace.json.gz`` that `jax.profiler.stop_trace`
writes beside the ``.xplane.pb``: it puts host and device events on one
clock and gives every device op the category XLA assigns it
(``hlo_category``: ``convolution fusion``, ``custom-call``,
``all-reduce``, ...).  Everything after `load` works on plain records,
so it can be checked on a synthetic trace:

- ``Op(name, start, end, category)``: one device operation, times in
  seconds; ``name`` is the HLO instruction's name (``fusion.12``,
  ``clip_sgd.3``, ``jvp_jit_batched_conv__.7``);
- ``Span(name, start, end)``: one host span the harness recorded with
  `jax.profiler.TraceAnnotation` (names start with ``chipbench.``).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]
SPAN_PREFIX = "chipbench."
WINDOW_START = SPAN_PREFIX + "window_start"
WINDOW_END = SPAN_PREFIX + "window_end"
# ops that contain other ops of the same line: busy, but not work of
# their own
CONTROL_FLOW = ("while", "conditional", "call")


@dataclass(frozen=True)
class Op:
    name: str
    start: float
    end: float
    category: str = ""


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    """Device ops per chip (keyed by its process name) and the harness's
    spans."""
    ops: Dict[str, List[Op]]
    spans: List[Span]

    def window(self) -> Interval:
        """From the end of the window-start marker to the start of the
        window-end marker."""
        starts = [s.end for s in self.spans if s.name == WINDOW_START]
        ends = [s.start for s in self.spans if s.name == WINDOW_END]
        if not starts or not ends:
            raise ValueError("the trace holds no window markers")
        return min(starts), max(ends)


def parse(events: List[Dict], op_line: str = "XLA Ops") -> Trace:
    """Records from Chrome-trace events (``ph`` ``M`` names processes
    and threads, ``ph`` ``X`` is a complete event, times in us)."""
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e.get("tid"))] = e["args"]["name"]
    ops: Dict[str, List[Op]] = defaultdict(list)
    spans: List[Span] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e.get("pid"), "")
        t0 = float(e["ts"]) * 1e-6
        t1 = t0 + float(e.get("dur", 0.0)) * 1e-6
        if proc.startswith("/device:") and "CPU" not in proc:
            if threads.get((e["pid"], e.get("tid"))) == op_line:
                cat = e.get("args", {}).get("hlo_category", "")
                ops[proc].append(Op(e["name"], t0, t1, cat))
        elif proc.startswith("/host:") and e["name"].startswith(SPAN_PREFIX):
            spans.append(Span(e["name"], t0, t1))
    return Trace(dict(ops), spans)


def events(trace_dir: str) -> List[Dict]:
    """The events of the newest ``*.trace.json.gz`` under ``trace_dir``."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .trace.json.gz under {trace_dir}")
    with gzip.open(files[-1], "rt") as f:
        return json.load(f)["traceEvents"]


def load(trace_dir: str) -> Trace:
    """Read the newest ``*.trace.json.gz`` under ``trace_dir``."""
    return parse(events(trace_dir))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def busy(ops: List[Op], lo: float, hi: float) -> List[Interval]:
    """The union of the ops' intervals inside ``[lo, hi]``."""
    return union(clip(((o.start, o.end) for o in ops), lo, hi))


def gaps(busy_iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between busy ones."""
    out, t = [], lo
    for a, b in busy_iv:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_gap(gap: Interval, spans: List[Span]) -> str:
    """The harness span that overlaps the gap most (the shortest of
    equals), or ``untraced`` when none does."""
    best, key = "untraced", (0.0, 0.0)
    for s in spans:
        ov = min(gap[1], s.end) - max(gap[0], s.start)
        if ov > 0 and (ov, s.start - s.end) > key:
            best, key = s.name[len(SPAN_PREFIX):], (ov, s.start - s.end)
    return best


def idle_by_span(gap_list: List[Interval], spans: List[Span]) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    inner = [s for s in spans if s.name not in (WINDOW_START, WINDOW_END)]
    for g in gap_list:
        out[label_gap(g, inner)] += g[1] - g[0]
    return dict(out)


def op_time(ops: List[Op], match: Callable[[Op], bool], lo: float,
            hi: float) -> float:
    """Device time of the matching ops inside ``[lo, hi]``."""
    return length(clip(((o.start, o.end) for o in ops if match(o)), lo, hi))


def top_ops(ops_by_chip: Dict[str, List[Op]], lo: float, hi: float,
            n: int = 10) -> List[List]:
    """Device time per op category (``category:name`` for custom calls,
    whose category says nothing), summed over chips, the ``n`` largest;
    control-flow ops, whose time their body's ops already hold, left
    out."""
    tot: Dict[str, float] = defaultdict(float)
    for ops in ops_by_chip.values():
        for o in ops:
            if o.category in CONTROL_FLOW:
                continue
            a, b = max(o.start, lo), min(o.end, hi)
            if b > a:
                key = o.category or "uncategorised"
                if o.category == "custom-call":
                    key += ":" + o.name.rsplit(".", 1)[0]
                tot[key] += b - a
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def summary(tr: Trace) -> Optional[Dict]:
    """Window length, busy seconds averaged over chips, idle share, idle
    gaps by host span, and the top device ops; None without device ops."""
    if not tr.ops:
        return None
    lo, hi = tr.window()
    window = hi - lo
    busy_s = [length(busy(ops, lo, hi)) for ops in tr.ops.values()]
    chip0 = sorted(tr.ops)[0]
    gap_list = gaps(busy(tr.ops[chip0], lo, hi), lo, hi)
    by_span = idle_by_span(gap_list, tr.spans)
    return {
        "window_s": window,
        "busy_s": sum(busy_s) / len(busy_s),
        "chips": len(tr.ops),
        "idle_gaps": [[k, v] for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])[:10]],
        "device_ops": top_ops(tr.ops, lo, hi),
    }
