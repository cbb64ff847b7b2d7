"""Share of its roofline the Parzen kernel reaches: the least time of a
call (``bench/roofline/parzen.py`` at the calls' shapes, peaks of
``bench/peaks.json``) over the kernel's device time per call in the trace,
in percent.  Each sampler call runs the kernel twice: on its good rows and
on its bad rows."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_roofline_parzen",
    Path(__file__).resolve().parents[1] / "roofline" / "parzen.py")
roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(roofline)


def read(rec: dict):
    kern = (rec.get("trace") or {}).get("kernels", {}).get("parzen")
    shapes = rec["launcher"].get("call_shapes")
    if not kern or not kern[0] or kern[1] <= 0 or not shapes:
        return None
    kind = rec["device"]["kind"]
    least = calls = 0
    for (ng, nb, d, c), count in shapes:
        least += count * (roofline.least_time(c, ng, d, kind)[0]
                          + roofline.least_time(c, nb, d, kind)[0])
        calls += 2 * count
    return 100.0 * (least / calls) / (kern[1] / kern[0])
