"""udf_ms.query: host milliseconds a query spends in UDF calls: the self
time of the program's `engine.udf` spans, their device calls
(`engine.device`) left out, over the window's queries."""
from spanwindow import self_ms_per_query


def read(run):
    return self_ms_per_query(run, "engine.udf")
