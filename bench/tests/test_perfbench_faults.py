"""The check turns ``correct`` false under its control and under each fault
planted beneath the timed path, at a size a test run can hold."""
import pytest

from _rehearse import rehearse

CELL = "campaign-tpe-overload"


def test_control_in_the_programs_place_fails_the_check():
    # the Parzen density contracted in three bfloat16 passes (the step
    # below the kernels' HIGHEST) at the cell's history of 2,000
    out = rehearse(CELL, 22, "--control", "high", seconds=25.0)
    assert out["correct"] is False, out["stderr"][-3000:]
    assert out["checks"]["rank_gap"] > 0.0


@pytest.mark.parametrize("fault,number", [
    ("proposal_order", "rank_gap"),        # an answer altered where made
    ("tell_value", "tells_lost"),          # a stored result altered
    ("tell_ignored", "tells_lost"),        # a tell leaves the state as it was
    ("split_order", "split_violations"),   # the worst rows taken as good
    ("liar_value", "liar_gap"),            # in-flight rows wrongly imputed
    ("wal_in_process", "tells_lost"),      # acknowledged before the OS has it
])
def test_a_planted_fault_fails_the_check(fault, number):
    out = rehearse(CELL, 3, "--fault", fault)
    assert out["correct"] is False, out["stderr"][-3000:]
    assert out["checks"][number] > 0
