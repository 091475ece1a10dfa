"""plan_cache_misses.query: compiled-plan cache misses of the query server
during the window (serve/query_server.py, engine/compile.PLAN_CACHE)."""


def read(run):
    return run.counters.get("plan_cache_misses")
