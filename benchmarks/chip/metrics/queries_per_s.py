"""queries_per_s: queries completed over the wall time of the whole rounds
of the window (host clock)."""


def read(run):
    return run.window.rate("queries")
