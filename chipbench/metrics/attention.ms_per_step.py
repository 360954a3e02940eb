"""attention.ms_per_step: device self time under the program's ``sdpa``
scope inside ``llm`` (scores, mask, softmax and PV of the LLM's
attention, forward and backward: the part a kernel replaces) per step in
the traced window (``scopes.py``)."""


def read(record):
    prog = (record.get("trace") or {}).get("program")
    if not prog or not record.get("steps"):
        return None
    s = sum(s for k, s in prog["scope_s"].items()
            if k.startswith("llm/") and "sdpa" in k.split("/"))
    return 1e3 * s / record["steps"]
