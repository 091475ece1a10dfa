"""assemble_ms.query: host milliseconds a query's fragments spend joining
their decoded input objects into one batch: the program's
`engine.fragment.assemble` spans, over the window's queries."""
from spanstats import layer_ms_per_query


def read(run):
    return layer_ms_per_query(run, "assemble")
