"""llm.head_ms_per_step: device self time under the program's ``lm_head``
scope (the unembedding and the cross-entropy, forward and backward) per
step in the traced window (``scopes.py``)."""


def read(record):
    prog = (record.get("trace") or {}).get("program")
    if not prog or not record.get("steps"):
        return None
    return 1e3 * sum(s for k, s in prog["scope_s"].items()
                     if k.split("/")[0] == "lm_head") / record["steps"]
