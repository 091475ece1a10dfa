"""query_p95_s: nearest-rank 95th percentile, over every query of the
window, of the wall time from the serve call until its answer is on the
host (host clock)."""
from windowstats import nearest_rank


def read(run):
    return nearest_rank(run.window.latencies("query"), 95)
