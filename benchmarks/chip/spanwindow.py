"""The program's spans (``repro.core.spans``) of the serve calls inside a
run's window, for readers that need more than ``spanstats``' split by
layer: a span's own self time, or a program counter summed over the
window's spans (``spans.count`` adds each count to the innermost open
span).

The calls kept are those ``spanstats`` keeps: ``engine.serve`` roots
that started at or after the window's start and ended by its end, with
every span below them. A program without the recorder, or without
counters, gives no reading.
"""
from __future__ import annotations

from spanstats import ROOT, DroppedSpans


def window_spans(run, counters: bool = False):
    """``(records, queries)`` of the window, or None where the program
    records no spans (or, with ``counters``, counts nothing into them)
    or the window served nothing."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    if counters and not hasattr(spans, "count"):
        return None
    records, dropped = spans.RING.snapshot()
    start, end = round(run.window.start * 1e9), round(run.window.end * 1e9)
    if dropped and (not records or records[0].end_ns >= start):
        raise DroppedSpans(
            f"the span ring pushed out {dropped} records, and the oldest "
            f"left closed inside the window: raise spans.CAPACITY")
    kept = {r.id: r for r in records
            if r.name == ROOT and r.parent is None
            and start <= r.start_ns and r.end_ns <= end}
    if not kept:
        return None
    for r in reversed(records):          # parents before their children
        if r.parent in kept:
            kept[r.id] = r
    queries = run.window.total("queries")
    return (list(kept.values()), queries) if queries else None


def self_ms_per_query(run, name: str):
    """Milliseconds a query spends in spans named ``name``, less what
    their child spans cover; None where no such span ran."""
    got = window_spans(run)
    if got is None:
        return None
    records, queries = got
    mine = {r.id: r for r in records if r.name == name}
    if not mine:
        return None
    ns = sum(r.end_ns - r.start_ns for r in mine.values())
    ns -= sum(r.end_ns - r.start_ns for r in records if r.parent in mine)
    return ns / 1e6 / queries


def counter_per_query(run, name: str):
    """A program counter summed over the window's spans, per query; None
    where the program has no counters."""
    got = window_spans(run, counters=True)
    if got is None:
        return None
    records, queries = got
    return sum(r.attrs.get(name, 0) for r in records) / queries
