"""Milliseconds a request waited in its dispatch lane's queue, from the
IO thread's parse to the lane's dequeue: the lanes' ``wait_ns`` over the
window per request they handled in it (``frontend`` of
``/api/v2/health``, read at the window's start and end)."""


def read(rec: dict):
    f0, f1 = rec["launcher"].get("frontend") or (None, None)
    if not f0 or not f1 or "per_lane" not in f0 or "per_lane" not in f1:
        return None

    def total(f, key):
        return sum(lane[key] for lane in f["per_lane"])
    handled = total(f1, "handled") - total(f0, "handled")
    if handled <= 0:
        return None
    return (total(f1, "wait_ns") - total(f0, "wait_ns")) / handled / 1e6
