"""device.idle_share: 1 - (union of device op intervals) / window, the
mean over the cell's devices, from the profiler trace."""


def read(record):
    trace = record.get("trace")
    devs = (trace or {}).get("devices") or {}
    if not devs:
        return None
    return 100.0 * sum(d["idle_share"] for d in devs.values()) / len(devs)
