"""Driver of ``tpch_sf1``: TPC-H tables at scale factor 1 served one query
per call through the program's ``QueryServer``.

Set-up generates only the tables the mix's queries read, from the seed,
stores them as the engine's compressed columnar objects, and warms up
every query of the mix on parameters drawn from the warm-up stream,
until two runs of each compile nothing. The window calls
``QueryServer.serve([QueryRequest(q)])`` once per query; the result is
on the host when the call returns. The check compares every answer of
the window with ``tpch_sf1_reference``.
"""
from __future__ import annotations

import concurrent.futures
import math
from pathlib import Path

import numpy as np

import streams
import workcount
from loader import load_module

REF = load_module(Path(__file__).resolve().with_name(
    "tpch_sf1_reference.py"))


def logical_query(name: str, params: dict):
    """The program's query builder for one drawn parameter set."""
    from repro.engine import queries
    if name == "q1":
        return queries.q1_logical(params["delta_days"])
    if name == "q6":
        return queries.q6_logical(REF.first_of_year(params["year"]),
                                  params["discount"], params["quantity"])
    if name == "q12":
        return queries.q12_logical(params["shuffle_partitions"],
                                   REF.first_of_year(params["year"]))
    raise ValueError(f"unknown query {name!r}")


def generate_tables(config: dict, names, seed: int) -> dict:
    return {t: REF.GENERATORS[t](
        config["tables"][t]["rows"],
        streams.rng(seed, "data", config["tables"][t]["table_index"]),
        config["scale_factor"])
        for t in names}


def store_tables(store, config: dict, tables: dict) -> dict:
    """Partition each table into the engine's objects; returns the keys.
    Partitions compress in parallel (zlib releases the GIL)."""
    from repro.engine import columnar
    from repro.engine.columnar import ColumnBatch
    jobs = []
    for t, cols in tables.items():
        n = len(next(iter(cols.values())))
        parts = config["tables"][t]["partitions"]
        bounds = np.linspace(0, n, parts + 1).astype(int)
        for p in range(parts):
            part = ColumnBatch({k: v[bounds[p]:bounds[p + 1]]
                                for k, v in cols.items()})
            jobs.append((t, f"tables/{t}/part-{p:05d}", part))
    with concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
        blobs = list(pool.map(lambda j: columnar.serialize(j[2]), jobs))
    keys: dict = {t: [] for t in tables}
    for (t, key, _), blob in zip(jobs, blobs):
        store.put(key, blob)
        keys[t].append(key)
    return keys


class SegmentReduceSpy:
    """Counts the program's segmented-reduction calls and the bytes each
    one needs, around the kernel's entry point in ``engine/compile.py``:
    the rows and segments it has to reduce, not the padded shapes it is
    handed."""

    def __init__(self):
        from repro.engine import compile as engine_compile
        self.calls = 0
        self.bytes = 0
        self.module = engine_compile
        self.inner = inner = engine_compile.segment_reduce

        def counted(vals, seg_ids, *, num_segments, **kw):
            shape = np.shape(vals)
            columns = 1 if len(shape) == 1 else shape[0]
            rows, segments = workcount.live_rows_and_segments(seg_ids)
            self.calls += 1
            self.bytes += workcount.segment_reduce_bytes(columns, rows,
                                                         segments)
            return inner(vals, seg_ids, num_segments=num_segments, **kw)

        engine_compile.segment_reduce = counted

    def remove(self):
        self.module.segment_reduce = self.inner


class TpchCell:
    def __init__(self, ctx, server, tables: dict):
        self.ctx = ctx
        self.server = server
        self.tables = tables
        self.served_items: list = []       # (query, params, result | None)
        self.spy = SegmentReduceSpy()

    def serve_one(self, name: str, params: dict):
        from repro.serve.query_server import QueryRequest
        with self.ctx.span("QueryServer.serve"):
            served = self.server.serve(
                [QueryRequest(logical_query(name, params))]).queries[0]
        return None if served.result.failure is not None \
            else served.result.result

    def serve(self, item: dict) -> None:
        """One query of the window; its answer is kept for the check."""
        result = self.serve_one(item["query"], item["params"])
        self.served_items.append((item["query"], item["params"], result))

    def counters(self) -> dict:
        from repro.engine import compile as engine_compile
        return {"plan_cache_misses": engine_compile.PLAN_CACHE.misses,
                "plan_cache_hits": engine_compile.PLAN_CACHE.hits,
                "interpreted_fallbacks":
                    engine_compile.FALLBACK_STATS["int32_join"],
                "segment_reduce_calls": self.spy.calls,
                "segment_reduce_bytes": self.spy.bytes}

    def served(self) -> dict:
        by_query: dict = {}
        for name, _, _ in self.served_items:
            by_query[name] = by_query.get(name, 0) + 1
        failed = sum(r is None for _, _, r in self.served_items)
        return {"attempted": len(self.served_items), "failed": failed,
                "succeeded": len(self.served_items) - failed,
                "by_query": by_query}

    def check(self) -> dict:
        """Largest relative error of any aggregate, and the number of
        answers whose groups or keys differ, over every answer of the
        window."""
        self.server = None
        self.spy.remove()
        limits = self.ctx.config["check"]["limits"]
        worst, wrong = 0.0, 0
        for name, params, got in answered(self.served_items):
            want = REF.QUERIES[name](self.tables, params)
            keys = self.ctx.config["queries"][name]["group_keys"]
            err = compare(got, want, keys)
            if err is None:
                wrong += 1
            else:
                worst = max(worst, err)
        return {"max_rel_err": {"value": worst,
                                "limit": limits["max_rel_err"]},
                "wrong_keys": {"value": wrong, "limit": limits["wrong_keys"]}}


def answered(items: list) -> list:
    """The served items that came back with an answer."""
    return [it for it in items if it[2] is not None]


def compare(got, want: dict, keys: list):
    """Worst relative error of the aggregates, or None where the answer's
    columns, groups or key values differ from the reference's."""
    got = {k: np.asarray(v) for k, v in got.items()}
    if sorted(got) != sorted(want):
        return None
    n = len(next(iter(want.values())))
    if any(len(v) != n for v in got.values()):
        return None
    og = np.lexsort([got[k] for k in reversed(keys)]) if keys \
        else np.arange(n)
    ow = np.lexsort([want[k] for k in reversed(keys)]) if keys \
        else np.arange(n)
    for k in keys:
        if not np.array_equal(got[k][og].astype(np.int64),
                              want[k][ow].astype(np.int64)):
            return None
    worst = 0.0
    for col in want:
        if col in keys:
            continue
        g = got[col][og].astype(np.float64)
        w = want[col][ow].astype(np.float64)
        diff = np.abs(g - w)
        rel = np.where(w != 0, diff / np.where(w != 0, np.abs(w), 1.0),
                       np.where(diff == 0, 0.0, math.inf))
        worst = max(worst, float(rel.max(initial=0.0)))
    return worst


def warm_up(ctx, cell: TpchCell) -> None:
    """Run each query of the mix on parameters drawn from the warm-up
    stream, skipping a parameter set already run, until ``quiet`` runs of
    it in a row compile nothing or it has drawn ``max_per_query`` sets."""
    spec = ctx.traffic["warmup"]
    quiet: dict = {}
    draws: dict = {}
    seen = set()

    def done(name):
        return quiet[name] >= spec["quiet"] \
            or draws[name] >= spec["max_per_query"]

    for items in ctx.rounds("warmup"):
        for item in items:
            name = item["query"]
            quiet.setdefault(name, 0)
            draws.setdefault(name, 0)
            if done(name):
                continue
            draws[name] += 1
            key = (name, tuple(sorted(item["params"].items())))
            if key in seen:
                continue
            seen.add(key)
            before = ctx.compiles
            cell.serve_one(name, item["params"])
            quiet[name] = quiet[name] + 1 if ctx.compiles == before else 0
        if all(done(q) for q in quiet):
            return


def setup(ctx, devices) -> TpchCell:
    from repro.core.storage_service import ObjectStore
    from repro.serve.query_server import QueryServer
    config = ctx.config
    names = sorted({t for item in next(ctx.rounds("warmup"))
                    for t in config["queries"][item["query"]]["tables"]})
    with ctx.phase("datagen"):
        tables = generate_tables(config, names, ctx.seed)
        store = ObjectStore()
        keys = store_tables(store, config, tables)
    with ctx.phase("server"):
        dep = config["deployment"]
        server = QueryServer(store, worker_budget=dep["worker_budget"],
                             backend=dep["backend"], mode=dep["mode"],
                             result_cache=dep["result_cache"])
        for t, ks in keys.items():
            server.register_table(t, ks)
        cell = TpchCell(ctx, server, tables)
    with ctx.phase("warmup"):
        warm_up(ctx, cell)
    return cell
