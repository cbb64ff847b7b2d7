"""Executions of the TPE proposal program in the traced window per ask
answered in it."""


def read(rec: dict):
    prog = (rec.get("trace") or {}).get("programs", {}).get("tpe_propose")
    if not prog or not rec["asks_answered"]:
        return None
    return prog[0] / rec["asks_answered"]
