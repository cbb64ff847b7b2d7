"""Runs ``bench/run.py`` as a subprocess and parses what it reports."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parents[1] / "run.py"


def rehearse(workload: str, seed: int, *extra: str, seconds: float = 3.0,
             rehearse: bool = True, run: Path = RUN) -> dict:
    """``run`` may be the ``run.py`` of a copy of the benchmark."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(run), "--workload", workload, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", "0", *extra]
    if rehearse:
        cmd.append("--rehearse")
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=400, cwd=run.parents[1])
    checks = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check (\w+): (\S+) ", proc.stderr, re.M)}
    correct = re.search(r"^correct: (True|False)$", proc.stderr, re.M)
    metrics = re.search(r"^metrics: (.*)$", proc.stderr, re.M)
    results = []
    for line in proc.stdout.splitlines():
        try:
            results.append(json.loads(line))
        except ValueError:
            pass
    return {"rc": proc.returncode, "checks": checks, "results": results,
            "metrics": None if metrics is None
            else json.loads(metrics.group(1)),
            "correct": None if correct is None else correct.group(1) == "True",
            "stderr": proc.stderr}
