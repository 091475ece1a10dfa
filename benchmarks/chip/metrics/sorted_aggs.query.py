"""sorted_aggs.query: keyed aggregations a query groups by a host sort
(`operators.group_boundaries`), not by the dense mixed-radix code of a
small integer key domain: the program counter `hash_agg.sorted`, counted
where `compile._run_hash_agg` chooses its grouping, over the window's
queries."""
from spanwindow import counter_per_query


def read(run):
    return counter_per_query(run, "hash_agg.sorted")
