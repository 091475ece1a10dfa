"""Traffic kind ``query_rounds``: one closed-loop client that sends whole
rounds of queries.

A mix file of this kind (``traffic/<mix>.json`` with ``"kind":
"query_rounds"``) lists under ``round`` the queries of one round, each by
name with its parameters; a parameter is drawn per query from the seed
(``streams.draw_param``). The client sends each query when the previous
answer is back.

The window starts rounds until its time is up and counts every round it
started, so no round is cut at an edge and the work per round does not
depend on where the window ends.

Every kind module exports ``rounds`` (the generator) and ``window`` (the
loop that drives the measured window); a new kind of traffic is a new
file beside this one.
"""
from __future__ import annotations

import time
from typing import Iterator

import streams
from windowstats import RoundRecord, Window


def rounds(mix: dict, config: dict, seed: int,
           stream: str) -> Iterator[list[dict]]:
    """Endless rounds of the mix; each round is a list of
    ``{"query": name, "params": {...}}``."""
    r = streams.rng(seed, stream)
    while True:
        yield [{"query": q["query"],
                "params": {k: streams.draw_param(v, r)
                           for k, v in q["params"].items()}}
               for q in mix["round"]]


def window(ctx, state, seconds: float, work: Iterator[list[dict]]) -> Window:
    """Whole rounds, started until ``seconds`` have passed; each query's
    latency is the wall time of its ``serve`` call."""
    w = Window(start=time.perf_counter(), rounds=[])
    with ctx.span("window"):
        while time.perf_counter() - w.start < seconds:
            items = next(work)
            latencies = []
            with ctx.span("round"):
                t0 = time.perf_counter()
                for item in items:
                    q0 = time.perf_counter()
                    state.serve(item)
                    latencies.append(time.perf_counter() - q0)
                t1 = time.perf_counter()
            w.rounds.append(RoundRecord(t0, t1, {"queries": len(items)},
                                        {"query": latencies}))
    return w
