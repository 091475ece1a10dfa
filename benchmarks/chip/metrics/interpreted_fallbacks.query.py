"""interpreted_fallbacks.query: compiled joins that fell back to the
interpreted path during the window (engine/compile.FALLBACK_STATS)."""


def read(run):
    return run.counters.get("interpreted_fallbacks")
