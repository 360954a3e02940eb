"""pipeline.exposed_collective_share: share of the window in which a
collective runs on a device and no other op does, the mean over the
cell's devices."""


def read(record):
    trace = record.get("trace")
    devs = (trace or {}).get("devices") or {}
    if not devs or not any(d["collective_s"] > 0 for d in devs.values()):
        return None
    share = [d["exposed_collective_s"] / trace["window_s"]
             for d in devs.values()]
    return 100.0 * sum(share) / len(share)
