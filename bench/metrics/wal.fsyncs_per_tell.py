"""WAL fsyncs over the window (``storage.fsyncs`` of ``/api/v2/health``,
read at the window's start and end) per tell acknowledged in it."""


def read(rec: dict):
    f0, f1 = rec["launcher"].get("fsyncs") or (None, None)
    tells = rec["tells_answered"]
    if f0 is None or f1 is None or not tells:
        return None
    return (f1 - f0) / tells
