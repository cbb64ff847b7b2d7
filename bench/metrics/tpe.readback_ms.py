"""Mean milliseconds a TPE proposal call waited on the device for its
result (``tpe.readback`` spans that start in the traced window)."""


def read(rec: dict):
    s = (rec["launcher"].get("spans") or {}).get("per_name", {}).get(
        "tpe.readback")
    if not s or not s[0]:
        return None
    return 1e3 * s[1] / s[0]
