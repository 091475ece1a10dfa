"""device_wait_ms.query: host milliseconds a query is blocked on the
device: the program's `engine.device` spans, each a jitted call or
kernel with the copies of its inputs and outputs, over the window's
queries. A wrapper around `compile.segment_reduce`, such as this
benchmark's byte counter, runs inside the span and is counted here."""
from spanstats import layer_ms_per_query


def read(run):
    return layer_ms_per_query(run, "device_wait")
