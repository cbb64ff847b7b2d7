"""Mean milliseconds of host work in one TPE proposal call: the mean
``tpe.propose`` span (split, padding, transfer, key, dispatch and
read-back) less the mean ``tpe.readback`` span inside it, over the spans
that start in the traced window."""


def read(rec: dict):
    per_name = (rec["launcher"].get("spans") or {}).get("per_name", {})
    propose, readback = per_name.get("tpe.propose"), per_name.get(
        "tpe.readback")
    if not propose or not propose[0] or not readback or not readback[0]:
        return None
    return 1e3 * (propose[1] / propose[0] - readback[1] / readback[0])
