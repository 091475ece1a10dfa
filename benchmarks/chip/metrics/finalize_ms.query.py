"""finalize_ms.query: host milliseconds a query spends in the
coordinator's merge and accounting: the self time of the program's
`engine.finalize` spans, over the window's queries."""
from spanstats import layer_ms_per_query


def read(run):
    return layer_ms_per_query(run, "finalize")
