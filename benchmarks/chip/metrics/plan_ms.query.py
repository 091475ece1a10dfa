"""plan_ms.query: host milliseconds a query spends in the planner: the
self time of the program's `engine.plan` spans (lowering, statistics,
plan-cache lookup, validation, stage compilation), over the window's
queries."""
from spanstats import layer_ms_per_query


def read(run):
    return layer_ms_per_query(run, "plan")
