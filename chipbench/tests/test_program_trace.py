"""The program's spans and scopes, and their reduction by
`chipbench.program_trace`: a traced run of a tiny CNN through
`Session.run` and through the grid runner, the scopes in the lowered
executables, and every reading on a synthetic trace."""
import re

import jax
import numpy as np
import pytest

from chipbench_tiny import ROOT  # noqa: F401  (puts the checkout on the path)

from chipbench import cells
from chipbench import program_trace as PT
from chipbench import trace as TR
from chipbench.harness import Context

STEPS = {"plan", "dispatch", "clock", "control", "fetch", "aggregate", "eval",
         "eval_fetch"}


def _spec(**overrides):
    from repro.api import ExperimentSpec
    from repro.config import SFLConfig

    base = dict(arch="vgg9-cifar-small", n_clients=3, partition="iid",
                n_train=180, n_test=45, seed=0, policy="fixed",
                estimate=False, rounds=4, eval_every=2, reconfigure_every=2,
                sfl=SFLConfig(agg_interval=2, lr=0.05))
    base.update(overrides)
    return ExperimentSpec(**base)


def _traced(tmp_path, fn):
    """Run ``fn`` inside a profiler session between the harness's window
    markers; returns its result and the trace."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0            # as the harness traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(TR.WINDOW_START):
            pass
        out = fn()
        with jax.profiler.TraceAnnotation(TR.WINDOW_END):
            pass
    finally:
        jax.profiler.stop_trace()
    return out, PT.load(str(tmp_path))


def _by_name(pt, name):
    return sorted((s for s in pt.spans if s.name == PT.PREFIX + name),
                  key=lambda s: s.start)


def _children(pt, seg):
    return [s for s in pt.spans if s.name != PT.SEGMENT
            and seg.start <= s.start and s.end <= seg.end]


def test_session_run_spans_each_segment_and_step(tmp_path):
    from repro.api import Session
    from repro.core.sfl import pow2_bucket

    sess = Session(_spec())
    res, pt = _traced(tmp_path, sess.run)
    segs = _by_name(pt, "segment")
    assert [(s.arg("t"), s.arg("rounds")) for s in segs] == [(0, 2), (2, 2)]
    for seg in segs:
        kids = _children(pt, seg)
        assert {s.name[len(PT.PREFIX):] for s in kids} == STEPS
        assert all(s.arg("t") == seg.arg("t") for s in kids)
    # every step lies in the segment whose round it carries
    assert sum(len(_children(pt, s)) for s in segs) == len(
        [s for s in pt.spans if s.name != PT.SEGMENT])
    # the fixed policy keeps one decision: rows and padded rows per segment
    b = np.asarray(res.b_history[0])
    for d in _by_name(pt, "dispatch"):
        assert d.arg("rows") == 2 * int(b.sum())
        assert d.arg("padded_rows") == 2 * 3 * pow2_bucket(int(b.max()))
    assert PT.row_use(pt) == pytest.approx(
        100 * b.sum() / (3 * pow2_bucket(int(b.max()))))
    by_seg = PT.steps_by_segment(pt)
    assert sorted(by_seg) == [0, 2] and set(by_seg[0]) == STEPS
    assert PT.host_ms(pt) > 0


def test_grid_runner_spans_each_shared_segment_and_bucket(tmp_path):
    from repro.api import Session
    from repro.core.sfl import pow2_bucket

    specs = [_spec(policy="hasfl"), _spec(policy="fixed")]
    res, pt = _traced(tmp_path, lambda: Session.run_grid(specs,
                                                         runner="grid"))
    segs = _by_name(pt, "segment")
    assert [s.arg("t") for s in segs] == [0, 2]
    crossed = False
    for k, seg in enumerate(segs):
        kids = _children(pt, seg)
        assert all(s.arg("t") == seg.arg("t") for s in kids)
        disp = [s for s in kids if s.name == PT.DISPATCH]
        bs = [np.asarray(r.b_history[k]) for r in res]
        buckets = {pow2_bucket(int(b.max())) for b in bs}
        crossed |= len(buckets) > 1
        assert len(disp) == len(buckets)
        assert sum(d.arg("rows") for d in disp) == 2 * sum(
            int(b.sum()) for b in bs)
        assert sum(d.arg("padded_rows") for d in disp) == 2 * 3 * sum(
            pow2_bucket(int(b.max())) for b in bs)
        # one plan and one fetch per bucket, one clock walk per segment,
        # and each cell's eval steps
        names = [s.name[len(PT.PREFIX):] for s in kids]
        assert names.count("plan") == names.count("fetch") == len(buckets)
        assert names.count("clock") == 1
        assert names.count("eval_fetch") == len(specs)
    assert crossed


def _hlo_scopes(compiled):
    """Every enclosing scope named in the executable's op_name paths."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', compiled.as_text()):
        out.update(path.split("/")[:-1])
    return out


@pytest.mark.parametrize("conv_impl", [None, "kernel"])
def test_scan_segment_carries_its_scopes(conv_impl):
    from repro.api import Session
    from repro.core import split as SP
    from repro.core.sfl import pow2_bucket

    sim = Session(_spec(conv_impl=conv_impl)).sim
    b = np.full(3, 4)
    b_pad = pow2_bucket(4)
    masks = jax.numpy.asarray(SP.client_unit_mask(
        sim.cfg, len(sim.units), 2))
    compiled = sim._scan_fn.lower(
        sim._stacked, jax.numpy.asarray(0, jax.numpy.int32),
        sim.store.segment_indices(2, b, b_pad), sim.store.row_mask(b, b_pad),
        masks, sim.store.arrays, None).compile()
    scopes = _hlo_scopes(compiled)
    assert {"gather", "client_grads", "update"} <= scopes
    assert (PT.IM2COL in scopes) == (conv_impl is not None)


def test_kernel_conv_scopes_its_patch_layout_and_not_its_matmul():
    from repro.kernels import ops

    x = jax.numpy.ones((2, 2, 8, 8, 3))
    w = jax.numpy.ones((2, 3, 3, 3, 4))
    b = jax.numpy.zeros((2, 4))
    for stride in (1, 2):
        f = jax.jit(jax.grad(lambda x, w, b: ops.batched_conv(
            x, w, b, stride=stride, impl="im2col").sum(), argnums=(0, 1)))
        paths = re.findall(r'op_name="([^"]*)"',
                           f.lower(x, w, b).compile().as_text())
        under = [p.split("/")[-1] for p in paths
                 if PT.IM2COL in p.split("/")[:-1]]
        assert {"pad", "slice", "concatenate"} <= set(under)
        assert "dot_general" not in under
        assert any(p.endswith("/dot_general") for p in paths)


# ---------------------------------------------------------------------------
# Synthetic trace, in the form a TPU trace has: op events carry their
# ``op_name`` path as ``tf_op`` (``<path>:``) and no module; the ``XLA
# Modules`` line holds one event per executable run
# ---------------------------------------------------------------------------

SCAN = "jit__scan_segment(123)"
EVAL = "jit__eval(456)"
BODY = "jit(_scan_segment)/while/body/closed_call/"
TPU = "/device:TPU:0"


def _op(name, t0, t1, path, cat="loop fusion"):
    args = {"hlo_category": cat}
    if path is not None:
        args["tf_op"] = path + ":"
    return {"ph": "X", "pid": 2, "tid": 20, "ts": t0 * 1e6,
            "dur": (t1 - t0) * 1e6, "name": name, "args": args}


def _run(module, t0, t1, run_id):
    return {"ph": "X", "pid": 2, "tid": 21, "ts": t0 * 1e6,
            "dur": (t1 - t0) * 1e6, "name": module,
            "args": {"run_id": run_id}}


def _host(name, t0, t1, **args):
    return {"ph": "X", "pid": 1, "tid": 10, "ts": t0 * 1e6,
            "dur": (t1 - t0) * 1e6, "name": name,
            "args": {k: str(v) for k, v in args.items()}}


def synthetic_events():
    """A 10 s window with two 5-round segments (t 30 and 35).  Each
    segment: plan, dispatch, clock and control 0.1 s each, a scan run of
    2.2 s (gather 0.3, client grads 1.0 of which im2col 0.3, update 0.5,
    0.2 under no scope, 0.2 idle between ops), a fetch that waits for
    it, then aggregate 0.3 s, eval 0.1 s with its run of 0.2 s, and the
    eval fetch."""
    ev = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 2, "name": "process_name", "args": {"name": TPU}},
        {"ph": "M", "pid": 2, "tid": 20, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 2, "tid": 21, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        _host(TR.WINDOW_START, 0.0, 0.5),
        _host(TR.WINDOW_END, 10.5, 10.6),
    ]
    for k, (t, a) in enumerate(((30, 0.5), (35, 5.5))):
        ev += [
            _host("repro.segment", a, a + 5.0, t=t, rounds=5),
            _host("repro.plan", a, a + 0.1, t=t),
            _host("repro.dispatch", a + 0.1, a + 0.2, t=t, rows=300,
                  padded_rows=320),
            _host("repro.clock", a + 0.2, a + 0.3, t=t),
            _host("repro.control", a + 0.3, a + 0.4, t=t),
            _host("repro.fetch", a + 0.4, a + 2.4, t=t),
            _host("repro.aggregate", a + 2.4, a + 2.7, t=t),
            _host("repro.eval", a + 2.7, a + 2.8, t=t),
            _host("repro.eval_fetch", a + 2.8, a + 3.1, t=t),
            # the scan run, 0.2 - 2.4 into the segment; idle 1.5 - 1.7
            _run(SCAN, a + 0.2, a + 2.4, f"{k}1"),
            _op("fusion.1", a + 0.2, a + 0.5, BODY + "gather/jit(_take)/gather"),
            _op("while.2", a + 0.5, a + 1.5, "jit(_scan_segment)/while",
                cat="while"),
            _op("fusion.3", a + 0.5, a + 0.8, BODY + "client_grads/jvp(jit("
                "batched_conv))/im2col/pad", cat="pad"),
            _op("fusion.4", a + 0.8, a + 1.5, BODY + "client_grads/jvp(jit("
                "batched_conv))/pallas_call", cat="custom-call"),
            _op("fusion.5", a + 1.7, a + 2.2, BODY + "update/jit(clip_sgd)/"
                "pallas_call", cat="custom-call"),
            # what XLA makes in the loop carries the while's path, or none
            _op("reverse.6", a + 2.2, a + 2.3, "jit(_scan_segment)/while",
                cat="reverse"),
            _op("copy.7", a + 2.3, a + 2.4, None, cat="data formatting"),
            # the eval run, 2.7 - 2.9
            _run(EVAL, a + 2.7, a + 2.9, f"{k}2"),
            _op("convolution.8", a + 2.7, a + 2.9, "jit(_eval)/conv",
                cat="convolution"),
        ]
    return ev


def _synthetic():
    ev = synthetic_events()
    return ev, PT.parse(ev, TR.parse(ev).window())


def test_parse_keeps_program_spans_op_paths_and_runs():
    _, pt = _synthetic()
    assert pt.window == pytest.approx((0.5, 10.5))
    assert len(pt.spans) == 18
    seg = PT.ProgramSpan("repro.segment", 0, 1, {"t": "35"})
    assert seg.arg("t") == 35 and seg.arg("rounds") is None
    ops = pt.ops[TPU]
    assert len(ops) == 16
    assert [o.name for o in ops if o.under(PT.IM2COL)] == ["fusion.3"] * 2
    assert ops[0].path == BODY + "gather/jit(_take)/gather"
    assert [o.path for o in ops if o.name == "copy.7"] == ["", ""]
    # the op's own name is not a scope: a take's ``gather`` op is not
    # under ``gather`` unless a scope of that name encloses it
    assert not PT.ScopedOp("g", 0, 1, path="jit(f)/jit(_take)/gather").under(
        "gather")
    runs = pt.runs[TPU]
    assert [(r.module, r.run_id, round(r.start - 0.5, 6), round(r.end - 0.5, 6))
            for r in runs[:2]] == [(SCAN, "01", 0.2, 2.4), (EVAL, "02", 2.7, 2.9)]
    assert len(runs) == 4 and PT.scan_runs(pt, TPU) == [runs[0], runs[2]]


def test_idle_splits_at_executable_runs():
    ev, pt = _synthetic()
    outer, inner = PT.idle_split(pt, TPU)
    # inside the scan run 1.5 - 1.7 is idle, in each segment
    assert TR.length(inner) == pytest.approx(0.4)
    # busy 2.2 s per segment of the 10 s window
    assert TR.length(outer) == pytest.approx(10 - 4.4 - 0.4)
    out_pct, in_pct = PT.idle_shares(pt)
    assert in_pct == pytest.approx(4.0)
    dev = cells.reader("device.idle_share")(
        Context(summary=TR.summary(TR.parse(ev))))
    assert out_pct + in_pct == pytest.approx(dev)


def test_scope_times_per_round():
    _, pt = _synthetic()
    # ten rounds in the window
    assert PT.scope_ms_per_round(pt, "client_grads", 10) == pytest.approx(
        2 * 1000 * 1.0 / 10)
    assert PT.scope_ms_per_round(pt, "update", 10) == pytest.approx(100)
    assert PT.scope_ms_per_round(pt, "gather", 10) == pytest.approx(60)
    assert PT.scope_ms_per_round(pt, PT.IM2COL, 10) == pytest.approx(60)
    assert PT.scope_ms_per_round(pt, "nowhere", 10) is None
    # of each scan run's 2.0 s of op time, 1.8 is under a scope
    assert PT.scoped_shares(pt, TPU) == pytest.approx([90.0, 90.0])


def test_host_steps_row_use_owners_and_dispatch_leads():
    _, pt = _synthetic()
    # host steps per segment: plan, dispatch, clock, control, aggregate,
    # eval (0.1 + 0.1 + 0.1 + 0.1 + 0.3 + 0.1); the fetches wait
    assert PT.host_ms(pt) == pytest.approx(800)
    assert PT.row_use(pt) == pytest.approx(100 * 300 / 320)
    assert PT.dispatch_leads(pt, TPU) == pytest.approx([0.1, 0.1])
    rep = PT.report(pt, 10)
    assert rep["segments"] == 2
    assert rep["step_ms_per_segment"]["fetch"] == pytest.approx(2000)
    # idle outside runs: before the scan run (plan, dispatch), between
    # the runs (aggregate), after the eval run (eval_fetch, then the
    # segment's tail)
    assert rep["idle_owners_s"] == pytest.approx({
        "plan": 0.2, "dispatch": 0.2, "aggregate": 0.6, "eval_fetch": 0.4,
        "segment": 3.8})
    assert rep["idle_outside_runs_s"] == pytest.approx(5.2)
    assert rep["idle_owned_pct"] == pytest.approx(100.0)
    assert rep["scoped_share_min_pct"] == pytest.approx(90.0)
    assert rep["dispatch_lead_min_ms"] == pytest.approx(100.0)
    assert rep["runs_by_module"] == {EVAL: 2, SCAN: 2}


def test_nothing_to_read_without_the_program_spans_and_scopes():
    ev = [e for e in synthetic_events()
          if not e["name"].startswith(PT.PREFIX)]
    for e in ev:
        e.get("args", {}).pop("tf_op", None)
    pt = PT.parse(ev, TR.parse(ev).window())
    assert PT.host_ms(pt) is None and PT.row_use(pt) is None
    assert PT.scope_ms_per_round(pt, "client_grads", 10) is None
    rep = PT.report(pt, 10)
    assert rep["loop.host_ms"] is None and rep["scoped_share_min_pct"] == 0.0
    assert rep["dispatch_lead_min_ms"] is None
    assert rep["idle_owners_s"] == pytest.approx({"none": 5.2})
    assert rep["idle_owned_pct"] == 0.0
    # the split of idle at the runs needs no program span
    assert sum(PT.idle_shares(pt)) == pytest.approx(56.0)
