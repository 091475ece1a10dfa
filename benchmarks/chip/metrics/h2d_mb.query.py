"""h2d_mb.query: megabytes (1e6 bytes) of host arrays a query hands to
the device: the `h2d_bytes` of the program's `engine.device` spans,
over the window's queries."""
from spanstats import window_stats


def read(run):
    stats = window_stats(run)
    if stats is None:
        return None
    return stats["h2d_bytes"] / 1e6 / stats["queries"]
