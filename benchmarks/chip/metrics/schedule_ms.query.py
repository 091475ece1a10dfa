"""schedule_ms.query: host milliseconds a query spends in the scheduler
itself: the self time of the program's `engine.schedule` spans, what
its fragments take left out, over the window's queries."""
from spanstats import layer_ms_per_query


def read(run):
    return layer_ms_per_query(run, "schedule")
