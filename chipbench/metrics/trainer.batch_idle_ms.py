"""trainer.batch_idle_ms: device idle time while the trainer's innermost
span is ``trainer.batch`` (the next batch drawn) per step in the traced
window (``scopes.py``)."""


def read(record):
    prog = (record.get("trace") or {}).get("program")
    if not prog or not record.get("steps"):
        return None
    return 1e3 * prog["idle_by_span_s"].get("trainer.batch", 0.0) \
        / record["steps"]
