"""The plain reference of the control loop (`reference/control.py`)
against the program's own latency model and optimizer, at full size on
the host: where both follow the paper, they agree.  HASFL is priced at
the paper's lr (5e-4), where its objective has a solution."""
import copy

import numpy as np
import pytest

from chipbench_tiny import ROOT

from chipbench import cells, harness
from chipbench import compare as CMP
from chipbench.counts import cnn
from chipbench.reference import control as CTL

CELL = cells.load_cell("vgg16.fixed16.auto", ROOT)
TRAFFIC = CELL["traffic"]
CTL_CONSTS = TRAFFIC["controller"]
PAPER_LR = 5e-4


def _program(lr, seed=2 ** 31 + 5):
    from repro.config import DeviceProfile, get_config
    from repro.core.bcd import HASFLOptimizer
    from repro.core.profiles import model_profile

    fleet = harness.make_fleet(TRAFFIC, seed)
    sfl = harness.build_spec(CELL["config"], dict(TRAFFIC, lr=lr), 0).sfl
    opt = HASFLOptimizer(model_profile(get_config(CELL["config"]["arch"])),
                         [DeviceProfile(**d) for d in fleet], sfl)
    return fleet, opt


def _conv(lr):
    return dict(CTL_CONSTS, lr=lr, agg_interval=TRAFFIC["agg_interval"])


def test_profile_and_round_times_match_the_program():
    fleet, opt = _program(TRAFFIC["lr"])
    prof = cnn.profile(CELL["config"], TRAFFIC)
    for key in ("rho", "bwd", "psi", "chi", "delta"):
        np.testing.assert_array_equal(prof[key], getattr(opt.profile, key))
    for cut in (1, 4, 16):
        b, cuts = np.arange(10, 30), np.full(20, cut)
        ts, ta = CTL.round_times(prof, fleet, CTL_CONSTS, b, cuts)
        assert ts == pytest.approx(opt.lat.t_split(b, cuts), rel=1e-15)
        assert ta == pytest.approx(opt.lat.t_agg(b, cuts), rel=1e-15)


def test_theta_matches_the_program_where_it_is_finite():
    fleet, opt = _program(PAPER_LR)
    prof = cnn.profile(CELL["config"], TRAFFIC)
    d = opt.solve(max_iter=4)
    got = CTL.theta(prof, fleet, CTL_CONSTS, _conv(PAPER_LR), d.b, d.cuts)
    assert np.isfinite(got) and got == pytest.approx(d.theta, rel=1e-12)
    b, cuts, best = CTL.solve(prof, fleet, CTL_CONSTS, _conv(PAPER_LR))
    assert best <= got
    assert CTL.decision_gap(prof, fleet, CTL_CONSTS, _conv(PAPER_LR), b, cuts,
                            best) == 0.0


def test_theta_is_infinite_without_a_corollary_1_solution():
    # at lr 0.05 the drift term alone passes epsilon
    prof = cnn.profile(CELL["config"], TRAFFIC)
    fleet = harness.make_fleet(TRAFFIC, 1)
    b, cuts = np.full(20, 16), np.full(20, 4)
    assert CTL.theta(prof, fleet, CTL_CONSTS, _conv(0.05), b, cuts) == np.inf
    assert CTL.solve(prof, fleet, CTL_CONSTS, _conv(0.05))[2] == np.inf


def test_clock_walks_rounds_and_aggregations():
    prof = cnn.profile(CELL["config"], TRAFFIC)
    fleet = harness.make_fleet(TRAFFIC, 1)
    b, cuts = np.full(20, 16), np.full(20, 4)
    ts, ta = CTL.round_times(prof, fleet, CTL_CONSTS, b, cuts)
    walk = CTL.clock(prof, fleet, CTL_CONSTS, [(b, cuts)] * 3, 15, 15, 45)
    assert walk[0] == ts
    assert walk[14] == pytest.approx(15 * ts + ta, rel=1e-14)
    assert walk[44] == pytest.approx(45 * ts + 3 * ta, rel=1e-14)


@pytest.mark.parametrize("first_cut_lower", [False, True])
def test_host_numbers_of_a_hasfl_run(first_cut_lower):
    """The HASFL branch of `compare.host_numbers` on the program's own
    decisions and clock walk, and on a first decision one cut lower."""
    fleet, opt = _program(PAPER_LR)
    d = opt.solve(max_iter=4)
    first = (d.b, d.cuts - 1 if first_cut_lower else d.cuts)
    decisions = [first, (d.b, d.cuts)]
    clocks, clock = [], 0.0
    for r in range(1, 16):
        b, cuts = decisions[0]
        clock += opt.lat.t_split(b, cuts)
        if r == 15:
            clock += opt.lat.t_agg(b, cuts)
        if r % 5 == 0:
            clocks.append((r, clock))
    cell = copy.deepcopy(CELL)
    cell["traffic"].update(policy="hasfl", lr=PAPER_LR)
    got = CMP.host_numbers(cell, {"fleet": fleet, "decisions": decisions,
                                  "clocks": clocks})
    assert got["clock_gap"] < 1e-12
    assert got["decisions_changed"] == float(first_cut_lower)
    assert 0.0 < got["decision_gap"] < (np.inf if first_cut_lower else 0.5)
    if first_cut_lower:
        assert got["decision_gap"] > 1.0
