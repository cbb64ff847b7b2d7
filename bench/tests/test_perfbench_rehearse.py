"""Each cell rehearsed end to end at a tiny size on the CPU: every phase
runs and the checks pass, but no result comes out, because there is no
chip.  And a run that is not a rehearsal finds no chip here and fails, as
does a run of a configuration whose sampler has no plug-in."""
import json
import shutil
import time

import pytest

import registry
from _rehearse import RUN, rehearse

BENCH = registry.benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_runs_every_phase_and_prints_no_result(workload):
    out = rehearse(workload, 2**31 + 5)
    assert out["rc"] == 1, out["stderr"][-3000:]
    assert out["results"] == []
    assert out["correct"] is True, out["stderr"][-3000:]
    assert out["checks"]["sampled_calls"] >= 1
    assert out["checks"]["tells_lost"] == out["checks"]["reports_lost"] == 0
    assert set(out["metrics"]) == {
        m["name"] for m in registry.metrics_of(BENCH, workload, False)}
    assert "rehearsal: no result" in out["stderr"]


def test_a_measurement_without_a_chip_fails():
    out = rehearse("campaign-tpe-overload", 7, rehearse=False)
    assert out["rc"] == 1 and out["results"] == []
    assert "FAIL:" in out["stderr"]


def _copy_with_a_gp_cell(root):
    """The benchmark's files copied to ``root`` with one configuration
    whose sampler is ``gp`` and a cell of it, as a later PR would add
    them: files and entries alone.  Whatever plug-ins the benchmark has,
    the copy has none for ``gp``."""
    here = RUN.parents[1]
    shutil.copytree(here / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "bench/samplers/gp.py").unlink(missing_ok=True)
    config = json.loads((here / "bench/configs/campaign-tpe.json").read_text())
    config["name"] = "campaign-gp"
    config["studies"]["history"] = [256, 511]
    config["studies"]["sampler"] = {"name": "gp", "n_candidates": 256}
    (root / "bench/configs/campaign-gp.json").write_text(json.dumps(config))
    bench = json.loads((here / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "campaign-gp-overload",
                               "config": "campaign-gp",
                               "traffic": "campaign-overload", "chips": 1,
                               "why": "a sampler with no plug-in"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root / "bench" / "run.py"


def test_a_sampler_without_a_plug_in_fails_at_once(tmp_path):
    run = _copy_with_a_gp_cell(tmp_path)
    t0 = time.monotonic()
    out = rehearse("campaign-gp-overload", 2**31 + 9, run=run)
    assert out["rc"] == 1 and out["results"] == []
    assert ("FAIL: no sampler plug-in named 'gp' (bench/samplers/gp.py)"
            in out["stderr"].splitlines())
    assert "Traceback" not in out["stderr"]
    assert "service:" not in out["stderr"]          # nothing was started
    assert time.monotonic() - t0 < 60
