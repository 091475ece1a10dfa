"""span_coverage.query: percent of the `engine.serve` calls' wall time
that the layers' spans cover (plan, schedule, store, codec, fragment
assembly, host glue, device, finalize); the rest is the serve call's
and the fragments' own glue."""
from spanstats import window_stats


def read(run):
    stats = window_stats(run)
    if stats is None or not stats["serve_s"]:
        return None
    return 100.0 * sum(stats["layers_s"].values()) / stats["serve_s"]
