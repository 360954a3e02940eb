"""pipeline.collective_ms_per_step: device time of collective ops
(all-gather, all-reduce, collective-permute, ...; by HLO op name) per
step, on the device that spends most in them."""


def read(record):
    trace = record.get("trace")
    devs = (trace or {}).get("devices") or {}
    coll = [d["collective_s"] for d in devs.values()]
    if not coll or max(coll) <= 0 or not record.get("steps"):
        return None
    return 1e3 * max(coll) / record["steps"]
