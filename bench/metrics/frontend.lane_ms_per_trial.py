"""Milliseconds the service spent executing requests (``http.ask``,
``http.report``, ``http.tell`` and ``http.request`` spans that start in
the traced window) per tell acknowledged in it.  All requests of one
study run on one dispatch lane, so this is the study's serial cost of one
trial: at 1,000 ms over its rate the lane is saturated."""

REQUEST = ("http.ask", "http.report", "http.tell", "http.request")


def read(rec: dict):
    per_name = (rec["launcher"].get("spans") or {}).get("per_name", {})
    total = sum(per_name.get(n, (0, 0.0))[1] for n in REQUEST)
    tells = rec["tells_answered"]
    if not total or not tells:
        return None
    return 1e3 * total / tells
