"""step.compile_s: the first step's wall time less the median steady
step of the window: what the first call spends compiling or loading."""
import statistics


def read(record):
    steady = record.get("step_seconds") or []
    if record.get("first_step_s") is None or not steady:
        return None
    return record["first_step_s"] - statistics.median(steady)
