"""host_ops_ms.query: host milliseconds a query spends in the fused
backend's host glue (sorts, gathers, padding, concatenation): the self
time of the program's `engine.ops` spans, device calls left out, over
the window's queries."""
from spanstats import layer_ms_per_query


def read(run):
    return layer_ms_per_query(run, "host_ops")
