"""data.host_ms_per_step: host time inside the benchmark's ``data`` span
(drawing a batch and handing it to the device) per step in the window."""


def read(record):
    trace = record.get("trace")
    count = (trace or {}).get("span_count", {}).get("data")
    if not count:
        return None
    return 1e3 * trace["span_s"]["data"] / count
