"""A configuration's model family is found by its ``reference`` key: the
CNN family's numbers as they were before it moved behind the lookup, a
stub family that serves the comparison and the readers alone, and the
``update`` scope that `clip_sgd_roofline` reads."""
import dataclasses
import glob
import os
import re
import sys
import types

import numpy as np
import pytest

from chipbench_tiny import ROOT, VGG9

from chipbench import cells, harness
from chipbench import compare as CMP
from chipbench import program_trace as PT
from chipbench.counts import cnn
from chipbench.harness import Context
from chipbench.reference import control as CTL

VGG16 = cells.load_cell("vgg16.fixed16", ROOT)["config"]

# vgg16-cifar's per-unit costs, as `reference/control.py` computed them
# before they moved to `counts/cnn.py`
VGG16_PROFILE = {
    "rho": [3538944, 79036416, 116785152, 192282624, 230031360, 305528832,
            381026304, 418775040, 494272512, 569769984, 588644352,
            607518720, 626393088, 626917376, 627441664, 627451904],
    "bwd": [7077888, 158072832, 233570304, 384565248, 460062720, 611057664,
            762052608, 837550080, 988545024, 1139539968, 1177288704,
            1215037440, 1252786176, 1253834752, 1254883328, 1254903808],
    "psi": [2097152, 524288, 1048576, 262144, 524288, 524288, 131072, 262144,
            262144, 65536, 65536, 65536, 16384, 16384, 16384, 320],
    "chi": [2097152, 524288, 1048576, 262144, 524288, 524288, 131072, 262144,
            262144, 65536, 65536, 65536, 16384, 16384, 16384, 320],
    "delta": [57344, 1239040, 3602432, 8325120, 17770496, 36653056,
              55535616, 93300736, 168814592, 244328448, 319842304,
              395356160, 470870016, 479275008, 487680000, 487844160],
    "params": [1792, 36928, 73856, 147584, 295168, 590080, 590080, 1180160,
               2359808, 2359808, 2359808, 2359808, 2359808, 262656, 262656,
               5130],
}


def test_vgg16_counts_are_pinned():
    assert cnn.train_flops(VGG16, {}) == 1483637760
    assert cnn.forward_flops(VGG16, {}) == 495676928
    assert cnn.param_count(VGG16) == 15245130


@pytest.mark.parametrize("key", sorted(VGG16_PROFILE))
def test_vgg16_profile_is_pinned(key):
    got = cnn.profile(VGG16, {})[key]
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got, np.asarray(VGG16_PROFILE[key],
                                                  np.float64))


def test_the_cnn_family_is_its_two_modules():
    from chipbench.reference import cnn as ref_cnn

    fam = cells.family(VGG16)
    assert fam.reference is ref_cnn and fam.counts is cnn


# ---------------------------------------------------------------------------
# A stub family, served by its own modules alone
# ---------------------------------------------------------------------------

N = 2
STUB_CFG = {"name": "stub-model", "reference": "stub"}
STUB_PROFILE = {"rho": np.array([1e9, 3e9]), "bwd": np.array([2e9, 6e9]),
                "psi": np.array([8e6, 8e3]), "chi": np.array([8e6, 8e3]),
                "delta": np.array([3.2e3, 9.6e3]),
                "params": np.array([100.0, 200.0])}


def _stub_modules(calls):
    ref = types.ModuleType("chipbench.reference.stub")
    counts = types.ModuleType("chipbench.counts.stub")

    def data(cfg, traffic, seed):
        calls.append(("data", seed))
        rng = np.random.default_rng(seed)
        return ((rng.standard_normal((traffic["n_train"], 3)),
                 rng.integers(0, 2, traffic["n_train"])),
                (rng.standard_normal((traffic["n_test"], 3)),
                 rng.integers(0, 2, traffic["n_test"])))

    def init_params(cfg, seed):
        calls.append(("init_params", seed))
        return {"0.w": np.zeros(3, np.float32)}

    class Trainer:
        def __init__(self, cfg, init, n, **kw):
            calls.append(("Trainer", n, kw["lr"], kw["agg_interval"]))
            self.t = 0

        def round(self, images, labels, idx, counts_, l_c):
            self.t += 1
            return np.full(N, 0.5 * self.t), {"0.w": 4.0, "0.b": 1e-12}

        def evaluate(self, images, labels):
            return 0.25 * self.t, 1.0

        def delta_norms(self):
            return {"0.w": 0.125 * self.t, "0.b": 0.0}

    ref.data, ref.init_params, ref.Trainer = data, init_params, Trainer
    ref.leaf_names = lambda cfg: ["0.w", "0.b"]

    def profile(cfg, traffic):
        calls.append(("profile",))
        return {k: v.copy() for k, v in STUB_PROFILE.items()}

    counts.train_flops = lambda cfg, traffic: 6 * traffic["seq_len"] * 1000
    counts.forward_flops = lambda cfg, traffic: 2 * traffic["seq_len"] * 1000
    counts.param_count = lambda cfg: 300
    counts.profile = profile
    return ref, counts


@pytest.fixture
def stub(monkeypatch):
    calls = []
    ref, counts = _stub_modules(calls)
    monkeypatch.setitem(sys.modules, "chipbench.reference.stub", ref)
    monkeypatch.setitem(sys.modules, "chipbench.counts.stub", counts)
    fleet = [dict(flops=1e12, up_bw=8e7, down_bw=3.7e8, fed_up_bw=8e7,
                  fed_down_bw=3.7e8, memory=3.2e10) for _ in range(N)]
    traffic = {
        "fleet": {"n": N}, "n_train": 12, "n_test": 4, "seq_len": 64,
        "lr": 0.05, "clip_norm": 1.0, "agg_interval": 5, "eval_every": 5,
        "reconfigure_every": 5, "trace_rounds": 10,
        "policy": "fixed(b=2,cut=1)",
        "check": {"rounds": 5, "delta_at": [5]},
        "controller": {"server_flops": 2e13, "server_fed_bw": 3.7e8},
    }
    return calls, {"config": STUB_CFG, "traffic": traffic}, fleet


def test_stub_family_replays_the_checked_rounds(stub):
    calls, cell, _ = stub
    seed = 2 ** 31 + 3
    prog = {"init": None, "b": [2, 2], "cuts": [1, 1],
            "idx": [[np.zeros((N, 2), int)] * 5]}
    ref = CMP.reference_readings(cell, seed, prog)
    assert calls == [("data", seed), ("init_params", seed),
                     ("Trainer", N, 0.05, 5)]
    assert sorted(ref["losses"]) == [1, 2, 3, 4, 5]
    np.testing.assert_array_equal(ref["losses"][5], [2.5, 2.5])
    assert ref["evals"] == {5: 1.25}
    assert ref["deltas"] == {5: {"0.w": 0.625, "0.b": 0.0}}
    assert ref["grad1"] == pytest.approx({"0.w": 2.0, "0.b": 1e-6})
    # the program's own readings against it: equal, so every gap is 0;
    # the leaf whose gradient is nought to rounding is left out
    same = dict(prog, losses=ref["losses"], evals=ref["evals"],
                deltas={5: {"0.w": 0.625, "0.b": 7.0}})
    got = CMP.numbers(same, ref, [5])
    assert got == {"loss1_gap": 0.0, "loss_gap": 0.0, "eval_loss_gap": 0.0,
                   "change5_gap": 0.0}


def test_stub_family_prices_the_clock(stub):
    calls, cell, fleet = stub
    ctl = cell["traffic"]["controller"]
    decision = (np.array([2, 2]), np.array([1, 1]))
    walk = CTL.clock(STUB_PROFILE, fleet, ctl, [decision] * 2, 5, 5, 10)
    prog = {"fleet": fleet, "decisions": [decision] * 2,
            "clocks": [(5, walk[4]), (10, walk[9])]}
    got = CMP.host_numbers(cell, prog)
    assert calls == [("profile",)]
    assert got == {"clock_gap": 0.0, "decisions_changed": 0.0,
                   "decision_mismatch": 0.0}
    late = dict(prog, clocks=[(5, walk[4]), (10, 1.5 * walk[9])])
    assert CMP.host_numbers(cell, late)["clock_gap"] == pytest.approx(0.5)


def test_stub_family_serves_the_family_readers(stub):
    _, cell, _ = stub
    window = (0.0, 2.0)
    update = "jit(_scan_segment)/while/body/update/add"
    program = PT.ProgramTrace(
        {"/device:TPU:0": [PT.ScopedOp("fusion.1", 0.5, 0.7, "loop fusion",
                                       update)]}, {}, [], window)
    ctx = Context(cfg=STUB_CFG, traffic=cell["traffic"],
                  summary={"window_s": 2.0, "chips": 1},
                  peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9},
                  b=np.array([2, 3]), program=program)
    # 10 rounds of 5 rows at 6 * 64 * 1000 operations, 2 evals of 4 rows
    # at 2 * 64 * 1000, over 2 s of a 1e12 peak
    flops = 10 * 5 * 384000 + 2 * 4 * 128000
    assert cells.reader("step_mfu")(ctx) == pytest.approx(
        100 * flops / 2e12)
    # 10 rounds of 12 bytes of 300 parameters for 2 clients, over 0.2 s
    assert cells.reader("clip_sgd_roofline")(ctx) == pytest.approx(
        100 * (10 * 12 * 2 * 300 / 1e9) / 0.2)


@pytest.mark.parametrize("part", ["reference", "counts"])
def test_a_missing_family_module_is_named(monkeypatch, part):
    ref, counts = _stub_modules([])
    mods = {"reference": ref, "counts": counts}
    other = "counts" if part == "reference" else "reference"
    monkeypatch.setitem(sys.modules, f"chipbench.{other}.nosuch",
                        mods[other])
    with pytest.raises(ModuleNotFoundError,
                       match=f"chipbench/{part}/nosuch.py is missing"):
        cells.family({"name": "x", "reference": "nosuch"})


def test_a_family_module_without_an_export_is_named(monkeypatch):
    ref, counts = _stub_modules([])
    del counts.profile
    monkeypatch.setitem(sys.modules, "chipbench.reference.stub", ref)
    monkeypatch.setitem(sys.modules, "chipbench.counts.stub", counts)
    with pytest.raises(AttributeError,
                       match="chipbench/counts/stub.py does not define "
                             "profile"):
        cells.family(STUB_CFG)


def test_harness_and_readers_name_no_family():
    """Only the CNN family's own files and its convolution reader name
    it; the harness and every other reader go through `cells.family`."""
    bench = cells.BENCH_DIR
    files = (glob.glob(os.path.join(bench, "*.py"))
             + glob.glob(os.path.join(bench, "metrics", "*.py"))
             + [os.path.join(bench, "reference", "control.py")])
    own = re.compile(r"(reference|counts)[./]cnn|import cnn|cifar_like")
    for path in files:
        if path.endswith(os.path.join("metrics", "conv_roofline.py")):
            continue
        with open(path) as f:
            assert not own.search(f.read()), path


# ---------------------------------------------------------------------------
# The spec, and the update scope
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq_len", [None, 128])
def test_build_spec_takes_seq_len_from_the_traffic(seq_len):
    from repro.api import ExperimentSpec

    cell = cells.load_cell("vgg16.fixed16", ROOT)
    traffic = dict(cell["traffic"])
    plain = harness.build_spec(cell["config"], traffic, 7)
    default = ExperimentSpec.__dataclass_fields__["seq_len"].default
    assert plain.seq_len == default
    if seq_len is not None:
        traffic["seq_len"] = seq_len
    spec = harness.build_spec(cell["config"], traffic, 7)
    assert spec.seq_len == (default if seq_len is None else seq_len)
    assert dataclasses.replace(spec, seq_len=default) == plain


WINDOW = (0.5, 6.0)
BODY = "jit(_scan_segment)/while/body/closed_call/"


def _scoped_trace():
    """Two chips; under ``update``: 0.4 s on chip 0 and 0.1 s of an op
    that runs past the window's end, 0.3 s on chip 1.  Not counted: the
    ``while`` (control flow), ops under other scopes, and an op that is
    itself named ``update`` under another scope."""
    ops = {
        "/device:TPU:0": [
            PT.ScopedOp("while.1", 0.9, 3.0, "while", BODY + "update/while"),
            PT.ScopedOp("fusion.2", 1.0, 1.4, "loop fusion",
                        BODY + "update/add"),
            PT.ScopedOp("fusion.3", 1.4, 2.0, "convolution fusion",
                        BODY + "client_grads/conv"),
            PT.ScopedOp("fusion.4", 2.0, 2.2, "loop fusion",
                        BODY + "client_grads/update"),
            PT.ScopedOp("fusion.5", 5.9, 6.3, "loop fusion",
                        BODY + "update/mul"),
        ],
        "/device:TPU:1": [
            PT.ScopedOp("clip_sgd.6", 1.0, 1.3, "custom-call",
                        BODY + "update/jit(clip_sgd)/pallas_call"),
            PT.ScopedOp("copy.7", 1.3, 1.5, "data formatting", ""),
        ],
    }
    return PT.ProgramTrace(ops, {}, [], WINDOW)


def test_clip_sgd_roofline_reads_the_update_scope():
    pt = _scoped_trace()
    assert PT.scope_seconds(pt, PT.UPDATE) == pytest.approx(0.8)
    assert PT.scope_ms_per_round(pt, PT.UPDATE, 10) == pytest.approx(40.0)
    traffic = {"trace_rounds": 10, "fleet": {"n": 20}}
    ctx = Context(cfg=VGG9, traffic=traffic, program=pt,
                  peaks=cells.peaks("TPU v5 lite"))
    least = 10 * 12 * 20 * cnn.param_count(VGG9) / 819e9
    assert cells.reader("clip_sgd_roofline")(ctx) == pytest.approx(
        100 * least / 0.8)


def test_clip_sgd_roofline_reads_nothing_without_the_scope():
    pt = _scoped_trace()
    bare = PT.ProgramTrace(
        {chip: [o for o in ops if not o.under(PT.UPDATE)]
         for chip, ops in pt.ops.items()}, {}, [], WINDOW)
    ctx = Context(cfg=VGG9, traffic={"trace_rounds": 10, "fleet": {"n": 20}},
                  program=bare, peaks=cells.peaks("TPU v5 lite"))
    assert cells.reader("clip_sgd_roofline")(ctx) is None
