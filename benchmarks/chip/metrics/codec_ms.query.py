"""codec_ms.query: host milliseconds a query spends encoding and decoding
columnar objects: the program's `engine.codec.encode` and
`engine.codec.decode` spans, over the window's queries."""
from spanstats import layer_ms_per_query


def read(run):
    return layer_ms_per_query(run, "codec")
