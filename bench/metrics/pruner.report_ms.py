"""Mean milliseconds of one pruning verdict on a report
(``pruner.should_prune`` spans that start in the traced window)."""


def read(rec: dict):
    s = (rec["launcher"].get("spans") or {}).get("per_name", {}).get(
        "pruner.should_prune")
    if not s or not s[0]:
        return None
    return 1e3 * s[1] / s[0]
