"""Mean milliseconds of one WAL record: encode, write and flush, and the
seal of a segment when it rotates (``wal.append`` spans that start in
the traced window)."""


def read(rec: dict):
    s = (rec["launcher"].get("spans") or {}).get("per_name", {}).get(
        "wal.append")
    if not s or not s[0]:
        return None
    return 1e3 * s[1] / s[0]
