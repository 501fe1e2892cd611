"""Trace reduction on a synthetic trace, and `load` on a recorded one."""
import glob
import os

import pytest

from chipbench_tiny import ROOT  # noqa: F401  (puts the checkout on the path)

from chipbench import cells
from chipbench import program_trace as PT
from chipbench import trace as TR
from chipbench.counts import cnn
from chipbench.harness import Context


def synthetic():
    ops = {
        "/device:TPU:0": [
            TR.Op("convolution.1", 1.0, 2.0, "convolution"),
            TR.Op("fusion.2", 1.5, 2.5, "loop fusion"),
            TR.Op("all-reduce.3", 2.4, 3.0, "all-reduce"),
            TR.Op("convolution.4", 4.0, 5.0, "convolution"),
            TR.Op("while.5", 1.0, 2.5, "while"),
        ],
        "/device:TPU:1": [
            TR.Op("convolution.1", 1.0, 3.0, "convolution"),
            TR.Op("all-reduce.3", 3.0, 3.5, "all-reduce"),
            TR.Op("clip_sgd.7", 3.5, 3.6, "custom-call"),
        ],
    }
    spans = [TR.Span(TR.WINDOW_START, 0.0, 0.5),
             TR.Span("chipbench.maybe_reconfigure", 3.1, 3.9),
             TR.Span("chipbench.eval_fetch", 5.0, 5.8),
             TR.Span(TR.WINDOW_END, 6.0, 6.1)]
    return TR.Trace(ops, spans)


def test_union_and_gaps():
    iv = TR.union([(1, 2), (1.5, 2.5), (4, 5)])
    assert iv == [(1, 2.5), (4, 5)]
    assert TR.gaps(iv, 0.5, 6.0) == [(0.5, 1), (2.5, 4), (5, 6.0)]
    assert TR.length(TR.clip(iv, 2.0, 4.5)) == pytest.approx(1.0)


def test_summary_busy_idle_and_labels():
    s = TR.summary(synthetic())
    assert s["window_s"] == pytest.approx(5.5)
    # chip 0 busy 1.0-3.0 and 4.0-5.0; chip 1 busy 1.0-3.6
    assert s["busy_s"] == pytest.approx((3.0 + 2.6) / 2)
    # chip 0's gaps: 0.5-1.0 (no span), 3.0-4.0 (mostly the controller),
    # 5.0-6.0 (mostly eval); a gap goes whole to the span over most of it
    gaps = dict(s["idle_gaps"])
    assert gaps == pytest.approx({"maybe_reconfigure": 1.0,
                                  "eval_fetch": 1.0, "untraced": 0.5})
    top = dict(s["device_ops"])        # the while loop is not counted
    assert set(top) == {"convolution", "loop fusion", "all-reduce",
                        "custom-call:clip_sgd"}
    assert top["convolution"] == pytest.approx(4.0)
    assert top["all-reduce"] == pytest.approx(1.1)


def test_op_time():
    tr = synthetic()
    conv = lambda o: "convolution" in o.category  # noqa: E731
    assert TR.op_time(tr.ops["/device:TPU:0"], conv, 0.5, 4.5) == \
        pytest.approx(1.5)


def _program(tr):
    """The synthetic trace by the program's scopes: chip 1's
    ``clip_sgd.7`` (0.1 s) under ``update``, every other op under
    none."""
    ops = {chip: [PT.ScopedOp(o.name, o.start, o.end, o.category,
                              "jit(_scan_segment)/while/body/update/"
                              "jit(clip_sgd)/pallas_call"
                              if o.name == "clip_sgd.7" else "")
                  for o in chip_ops]
           for chip, chip_ops in tr.ops.items()}
    return PT.ProgramTrace(ops, {}, [], tr.window())


def _ctx(tr, **kw):
    cfg = {"reference": "cnn", "image_size": 4, "in_channels": 1,
           "conv_channels": [1], "pool_after": [], "fc_dims": [],
           "n_classes": 2, "residual": False}
    import numpy as np

    base = dict(trace=tr, summary=TR.summary(tr), program=_program(tr),
                cfg=cfg,
                traffic={"trace_rounds": 10, "eval_every": 5, "n_test": 4,
                         "fleet": {"n": 2}},
                b=np.array([2, 2]), peaks=cells.peaks("TPU v5 lite"))
    base.update(kw)
    return Context(**base)


def test_readers_on_the_synthetic_trace():
    tr = synthetic()
    ctx = _ctx(tr)
    idle = cells.reader("device.idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - 2.8 / 5.5))
    roof = cells.reader("conv_roofline")(ctx)
    assert 0 < roof < 1e-3
    # 12 bytes per parameter per client per round, over the 0.1 s of
    # device time under the update scope
    least = 10 * 12 * 2 * cnn.param_count(ctx.cfg) / 819e9
    assert cells.reader("clip_sgd_roofline")(ctx) == pytest.approx(
        100 * least / 0.1)


def test_readers_read_nothing_without_a_trace():
    ctx = Context(trace=None, summary=None, program=None, peaks=None)
    for m in ("device.idle_share", "step_mfu", "conv_roofline",
              "clip_sgd_roofline"):
        assert cells.reader(m)(ctx) is None


def test_load_reads_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0            # as the harness traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(TR.WINDOW_START):
        pass
    with jax.profiler.TraceAnnotation("chipbench.dispatch"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation(TR.WINDOW_END):
        pass
    jax.profiler.stop_trace()
    assert glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                  "*.trace.json.gz"))
    tr = TR.load(str(tmp_path))
    names = {s.name for s in tr.spans}
    assert {TR.WINDOW_START, TR.WINDOW_END, "chipbench.dispatch"} <= names
    lo, hi = tr.window()
    assert hi > lo
