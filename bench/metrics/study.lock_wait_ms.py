"""Mean milliseconds a request waited to acquire its study's lock
(``study.lock_wait`` spans that start in the traced window)."""


def read(rec: dict):
    s = (rec["launcher"].get("spans") or {}).get("per_name", {}).get(
        "study.lock_wait")
    if not s or not s[0]:
        return None
    return 1e3 * s[1] / s[0]
