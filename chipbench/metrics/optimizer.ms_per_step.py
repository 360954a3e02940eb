"""optimizer.ms_per_step: device self time under the program's
``optimizer`` and ``health`` scopes (the AdamW update and the guarded
step's health gate: global norm, spike score, the selects of the new
state) per step in the traced window (``scopes.py``)."""


def read(record):
    prog = (record.get("trace") or {}).get("program")
    if not prog or not record.get("steps"):
        return None
    s = sum(s for k, s in prog["scope_s"].items()
            if k.split("/")[0] in ("optimizer", "health"))
    return 1e3 * s / record["steps"]
