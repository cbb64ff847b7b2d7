"""Milliseconds of the traced window spent in WAL compaction (each
``DurableStorage.compact`` timed by the launcher, clipped to the window):
the background fold of sealed segments into a new snapshot, which holds
the interpreter lock while it serializes the whole store."""


def read(rec: dict):
    spans = rec["launcher"].get("compaction_s")
    return None if spans is None else 1e3 * sum(spans)
