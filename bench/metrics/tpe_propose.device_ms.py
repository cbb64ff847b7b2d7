"""Device time of one execution of the TPE proposal program
(``_tpe_propose``), averaged over its executions in the traced window."""


def read(rec: dict):
    prog = (rec.get("trace") or {}).get("programs", {}).get("tpe_propose")
    if not prog or not prog[0]:
        return None
    return 1e3 * prog[1] / prog[0]
