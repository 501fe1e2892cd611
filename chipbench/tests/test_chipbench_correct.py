"""`correct` on tiny cells on the CPU: a sound run of the program passes
the cell's limits, and the control (the reference at bfloat16, its
clock at float32, put in the program's place) fails them."""
import numpy as np
import pytest

from chipbench_tiny import VGG9, tiny_cell

from chipbench import compare as CMP
from chipbench import harness

CASES = ["vgg16.fixed16", "vgg16.fixed16.auto"]
SEED = 2 ** 31 + 17


def _passes(cell, got):
    limits = cell["limits"]["limits"]
    return all(got[k] <= v["limit"] for k, v in limits.items())


@pytest.fixture(scope="module", params=CASES)
def sound(request):
    cell = tiny_cell(request.param, VGG9)
    probe = harness.run_program(cell, SEED, 0.0, False)
    prog = harness.readings(probe)
    return cell, probe, prog, CMP.reference_readings(cell, SEED, prog)


def test_sound_run_passes(sound):
    cell, probe, prog, ref = sound
    assert probe.compiles["window"] == 0
    got = CMP.numbers(prog, ref, cell["traffic"]["check"]["delta_at"])
    got.update(CMP.host_numbers(cell, prog))
    assert _passes(cell, got), got


def test_control_fails(sound):
    cell, _, prog, ref = sound
    ctl = CMP.reference_readings(cell, SEED, prog, dtype="bfloat16",
                                 precision="default")
    got = CMP.numbers(ctl, ref, cell["traffic"]["check"]["delta_at"])
    got.update(CMP.host_numbers(cell, prog, dtype=np.float32))
    limits = cell["limits"]["limits"]
    assert any(got[k] > v["limit"] for k, v in limits.items() if k in got), got
