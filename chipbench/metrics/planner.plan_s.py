"""planner.plan_s: host clock around the plan search and its lint
(``resolve_plan``, and the cell's own ``parallelize`` where it has one)."""


def read(record):
    return record.get("plan_s")
