"""trainer.health_read_ms: host time inside the trainer's
``trainer.health_read`` span (the step's one host sync: how long the host
waits for the device) per read in the traced window (``scopes.py``)."""


def read(record):
    prog = (record.get("trace") or {}).get("program")
    span = (prog or {}).get("spans", {}).get("trainer.health_read")
    if not span or not span["count"]:
        return None
    return 1e3 * span["s"] / span["count"]
