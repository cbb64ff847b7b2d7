"""Mean milliseconds an ask spent pulling new completions and the pending
view into its study's observation cache (``obs_cache.sync`` spans that
start in the traced window)."""


def read(rec: dict):
    s = (rec["launcher"].get("spans") or {}).get("per_name", {}).get(
        "obs_cache.sync")
    if not s or not s[0]:
        return None
    return 1e3 * s[1] / s[0]
