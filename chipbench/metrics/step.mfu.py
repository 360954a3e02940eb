"""step.mfu: model FLOPs of the steps in the traced window (``flops.py``,
frozen-aware, no recompute) over the window and the chips' bf16 peak."""


def read(record):
    trace = record.get("trace")
    if not trace or not record.get("steps"):
        return None
    achieved = record["step_flops"] * record["steps"] / trace["window_s"]
    return 100.0 * achieved / (record["chips"] * record["peak_flops"])
