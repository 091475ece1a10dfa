"""Host time of each layer of the query path, per query, from the
program's own spans (``repro.core.spans``).

The program records a span at every layer boundary of the query path,
on ``time.perf_counter``, the clock the window is timed on. This
reduction keeps the ``engine.serve`` calls that started at or after the
window's start and ended by its end, with every span below them, sums
the spans' self time (their duration less what their child spans
cover) by layer, and divides by the window's queries. Self times of one
call add up to its duration, so the layers and the rest (the self time
of ``engine.serve`` and ``engine.fragment``: worker glue and report
building) split each call's wall time.

A program without the recorder gives no reading: ``window_stats``
returns None. A ring that has pushed out records of the window fails
the run rather than read low.
"""
from __future__ import annotations

import collections

ROOT = "engine.serve"
LAYERS = {
    "engine.plan": "plan",
    "engine.schedule": "schedule",
    "engine.store.get": "store",
    "engine.store.put": "store",
    "engine.codec.decode": "codec",
    "engine.codec.encode": "codec",
    "engine.fragment.assemble": "assemble",
    "engine.ops": "host_ops",
    "engine.device": "device_wait",
    "engine.finalize": "finalize",
}


class DroppedSpans(RuntimeError):
    """The program's span ring pushed out records of the window."""


def reduce_spans(records, dropped: int, start_ns: int, end_ns: int) -> dict:
    """Seconds by layer over the serve calls inside ``[start_ns,
    end_ns]``: ``{"layers_s": {layer: s}, "rest_s", "serve_s",
    "h2d_bytes", "calls", "spans"}``. ``records`` are in the order they
    closed, as the ring keeps them; a parent closes after its children."""
    if dropped and (not records or records[0].end_ns >= start_ns):
        raise DroppedSpans(
            f"the span ring pushed out {dropped} records, and the oldest "
            f"left closed inside the window: raise spans.CAPACITY")
    kept = {r.id: r for r in records
            if r.name == ROOT and r.parent is None
            and start_ns <= r.start_ns and r.end_ns <= end_ns}
    calls = len(kept)
    for r in reversed(records):          # parents before their children
        if r.parent in kept:
            kept[r.id] = r
    covered: dict = collections.Counter()
    for r in kept.values():
        if r.parent is not None:
            covered[r.parent] += r.end_ns - r.start_ns
    layers = dict.fromkeys(LAYERS.values(), 0.0)
    rest = serve = 0.0
    h2d = 0
    for r in kept.values():
        self_s = (r.end_ns - r.start_ns - covered[r.id]) / 1e9
        layer = LAYERS.get(r.name)
        if layer is None:
            rest += self_s
        else:
            layers[layer] += self_s
        if r.name == ROOT:
            serve += (r.end_ns - r.start_ns) / 1e9
        h2d += r.attrs.get("h2d_bytes", 0)
    return {"layers_s": layers, "rest_s": rest, "serve_s": serve,
            "h2d_bytes": h2d, "calls": calls, "spans": len(kept)}


def window_stats(run) -> dict | None:
    """``reduce_spans`` over the run's window, with its ``queries``; None
    where the program records no spans or the window served nothing."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    records, dropped = spans.RING.snapshot()
    stats = reduce_spans(records, dropped, round(run.window.start * 1e9),
                         round(run.window.end * 1e9))
    stats["queries"] = run.window.total("queries")
    if not stats["calls"] or not stats["queries"]:
        return None
    return stats


def layer_ms_per_query(run, layer: str) -> float | None:
    stats = window_stats(run)
    if stats is None:
        return None
    return 1e3 * stats["layers_s"][layer] / stats["queries"]
