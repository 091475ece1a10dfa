"""exchange_mb.query: megabytes (1e6 bytes) of shuffle frames a query's
fragments write to the exchange: the program counter `exchange_bytes`,
counted where a fragment puts each frame, over the window's queries."""
from spanwindow import counter_per_query


def read(run):
    n = counter_per_query(run, "exchange_bytes")
    return None if n is None else n / 1e6
