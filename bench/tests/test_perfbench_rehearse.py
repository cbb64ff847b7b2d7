"""Each cell rehearsed end to end at a tiny size on the CPU: every phase
runs and the checks pass, but no result comes out, because there is no
chip.  And a run that is not a rehearsal finds no chip here and fails."""
import pytest

from _rehearse import rehearse


@pytest.mark.parametrize("workload", ["campaign-tpe-overload"])
def test_rehearsal_runs_every_phase_and_prints_no_result(workload):
    out = rehearse(workload, 2**31 + 5)
    assert out["rc"] == 1, out["stderr"][-3000:]
    assert out["results"] == []
    assert out["correct"] is True, out["stderr"][-3000:]
    assert out["checks"]["sampled_calls"] >= 1
    assert out["checks"]["tells_lost"] == out["checks"]["reports_lost"] == 0
    assert "rehearsal: no result" in out["stderr"]


def test_a_measurement_without_a_chip_fails():
    out = rehearse("campaign-tpe-overload", 7, rehearse=False)
    assert out["rc"] == 1 and out["results"] == []
    assert "FAIL:" in out["stderr"]
