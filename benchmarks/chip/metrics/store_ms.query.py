"""store_ms.query: host milliseconds a query spends in object reads and
writes: the program's `engine.store.get` and `engine.store.put` spans,
over the window's queries."""
from spanstats import layer_ms_per_query


def read(run):
    return layer_ms_per_query(run, "store")
