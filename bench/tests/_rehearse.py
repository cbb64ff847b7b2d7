"""Runs ``bench/run.py`` as a subprocess and parses what it reports."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parents[1] / "run.py"


def rehearse(workload: str, seed: int, *extra: str, seconds: float = 3.0,
             rehearse: bool = True) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(RUN), "--workload", workload, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", "0", *extra]
    if rehearse:
        cmd.append("--rehearse")
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=400, cwd=RUN.parents[1])
    checks = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^check (\w+): (\S+) ", proc.stderr, re.M)}
    correct = re.search(r"^correct: (True|False)$", proc.stderr, re.M)
    results = []
    for line in proc.stdout.splitlines():
        try:
            results.append(json.loads(line))
        except ValueError:
            pass
    return {"rc": proc.returncode, "checks": checks, "results": results,
            "correct": None if correct is None else correct.group(1) == "True",
            "stderr": proc.stderr}
