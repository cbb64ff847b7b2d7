"""Milliseconds of the traced window in which the service process's
garbage collector ran (``gc.callbacks`` in the launcher, clipped to the
window): every thread of the service waits while it runs."""


def read(rec: dict):
    spans = rec["launcher"].get("gc_s")
    return None if spans is None else 1e3 * sum(spans)
