"""encoder.ms_per_step: device self time under the program's ``encoder``
scope (the frozen vision tower, forward only) per step in the traced
window (``scopes.py``)."""


def read(record):
    prog = (record.get("trace") or {}).get("program")
    if not prog or not record.get("steps"):
        return None
    return 1e3 * sum(s for k, s in prog["scope_s"].items()
                     if k.split("/")[0] == "encoder") / record["steps"]
