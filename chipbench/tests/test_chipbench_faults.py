"""A run with the timed path broken underneath comes out not correct:
the harness is driven past its look for a chip, on tiny cells on the
CPU, once for each fault the cells can have."""
import pytest

from chipbench_tiny import VGG9, tiny_cell

from chipbench import compare as CMP
from chipbench import harness

SEED = 2 ** 31 + 29
CASES = ["vgg16.fixed16", "vgg16.fixed16.auto"]


@pytest.fixture(scope="module", params=CASES)
def reference(request):
    cell = tiny_cell(request.param, VGG9)
    prog = harness.readings(harness.run_program(cell, SEED, 0.0, False))
    return cell, CMP.reference_readings(cell, SEED, prog)


@pytest.mark.parametrize("fault", harness.FAULTS)
def test_fault_is_not_correct(reference, fault):
    cell, ref = reference
    got = harness.readings(harness.run_program(cell, SEED, 0.0, False, fault))
    nums = CMP.numbers(got, ref, cell["traffic"]["check"]["delta_at"])
    nums.update(CMP.host_numbers(cell, got))
    limits = cell["limits"]["limits"]
    assert any(nums[k] > v["limit"] for k, v in limits.items()), nums
