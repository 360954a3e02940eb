"""pipeline.stage_imbalance: how unevenly the pipeline's chips work in
the traced window, 100 x (max - min) / max over the chips of their
device time outside collective ops (``busy_s - collective_s``); 0 where
every chip computes as long as the busiest. Busy time alone does not
tell the chips apart: a chip that waits for a handoff waits inside the
collective, and the runner's rolled loop keeps an op open on every chip
for the whole step. None on fewer than two chips."""


def read(record):
    trace = record.get("trace")
    devs = (trace or {}).get("devices") or {}
    work = [d["busy_s"] - d["collective_s"] for d in devs.values()]
    if len(work) < 2 or max(work) <= 0:
        return None
    return 100.0 * (max(work) - min(work)) / max(work)
