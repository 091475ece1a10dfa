"""Driver of ``tpcxbb_sf1``: TPCx-BB Q3 over the clickstreams at scale
factor 1, served one query per call through the program's
``QueryServer``.

Set-up builds one query first, so that a program whose query builder
lacks this query's parameters fails at once; then it generates the
clickstreams and the item table from the seed, stores them as the
engine's compressed columnar objects (the item table is the query's
broadcast side input), and warms the query up on parameters drawn from
the warm-up stream until two runs compile nothing. The window calls
``QueryServer.serve([QueryRequest(q)])`` once per query; the check
compares every answer of the window with ``tpcxbb_sf1_reference`` over
the whole table.
"""
from __future__ import annotations

import concurrent.futures
from pathlib import Path

import numpy as np

import streams
from loader import load_module

REF = load_module(Path(__file__).resolve().with_name(
    "tpcxbb_sf1_reference.py"))

ITEM_KEY = "tables/item/part-00000"


def session_window_bytes(calls: int, rows: int, items: int) -> int:
    """Bytes the session window needs: each row's user, timestamp and
    item (int32) and click type (int8) read once, and per call the item
    categories (int32) read and one int32 count per item written."""
    return 13 * rows + 8 * items * calls


def logical_query(params: dict, item_key: str = ITEM_KEY):
    """The program's query builder for one drawn parameter set."""
    from repro.engine import queries
    return queries.bb_q3_logical(
        item_key, params["target_category"], views=params["views"],
        seconds=params["seconds"],
        shuffle_partitions=params["shuffle_partitions"])


def generate_tables(config: dict, seed: int) -> dict:
    tables = {}
    for name, spec in config["tables"].items():
        r = streams.rng(seed, "data", spec["table_index"])
        if name == "clickstreams":
            tables[name] = REF.gen_clickstreams(
                spec["rows"], r, spec["users"], spec["items"], spec["days"])
        else:
            tables[name] = REF.gen_item(spec["rows"], r, spec["categories"])
    return tables


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


class BbCell:
    def __init__(self, ctx, server, tables: dict, item_key: str):
        self.ctx = ctx
        self.server = server
        self.tables = tables
        self.item_key = item_key
        self.served_items: list = []       # (query, params, result | None)

    def serve_one(self, params: dict):
        from repro.serve.query_server import QueryRequest
        with self.ctx.span("QueryServer.serve"):
            served = self.server.serve(
                [QueryRequest(logical_query(params, self.item_key))]
            ).queries[0]
        return None if served.result.failure is not None \
            else served.result.result

    def serve(self, item: dict) -> None:
        """One query of the window; its answer is kept for the check."""
        self.served_items.append((item["query"], item["params"],
                                  self.serve_one(item["params"])))

    def counters(self) -> dict:
        from repro.core import spans
        from repro.engine import compile as engine_compile
        c = spans.COUNTERS
        fallbacks = engine_compile.FALLBACK_STATS
        items = self.ctx.config["tables"]["item"]["rows"]
        return {"plan_cache_misses": engine_compile.PLAN_CACHE.misses,
                "plan_cache_hits": engine_compile.PLAN_CACHE.hits,
                "interpreted_fallbacks": fallbacks["int32_join"]
                + fallbacks["int32_udf"],
                "session_window_calls": c["session_window.calls"],
                "session_window_rows": c["session_window.rows"],
                "session_window_purchases": c["session_window.purchases"],
                "session_window_bytes": session_window_bytes(
                    c["session_window.calls"], c["session_window.rows"],
                    items),
                "exchange_bytes": c["exchange_bytes"]}

    def served(self) -> dict:
        failed = sum(r is None for _, _, r in self.served_items)
        return {"attempted": len(self.served_items), "failed": failed,
                "succeeded": len(self.served_items) - failed,
                "by_query": {"bb_q3": len(self.served_items)}}

    def check(self, rounding=REF.exact) -> dict:
        """Largest difference of any item's count, and the number of
        answers whose viewed items differ, over every answer of the
        window, against the whole table's sessions, their timestamps
        passed through ``rounding`` (``REF.bfloat16`` for the
        control)."""
        self.server = None
        limits = self.ctx.config["check"]["limits"]
        sessions = REF.Sessions(self.tables["clickstreams"], rounding)
        categories = self.tables["item"]["i_category_id"]
        worst, wrong = 0, 0
        for _, params, got in answered(self.served_items):
            err = compare(got, sessions.bb_q3(categories, params))
            if err is None:
                wrong += 1
            else:
                worst = max(worst, err)
        return {"max_abs_err": {"value": worst,
                                "limit": limits["max_abs_err"]},
                "wrong_keys": {"value": wrong, "limit": limits["wrong_keys"]}}


def answered(items: list) -> list:
    """The served items that came back with an answer."""
    return [it for it in items if it[2] is not None]


def compare(got, want: dict):
    """Largest absolute difference of the view counts, or None where the
    answer's columns or viewed items differ from the reference's."""
    got = {k: np.asarray(v) for k, v in got.items()}
    if sorted(got) != sorted(want):
        return None
    order = np.argsort(got["viewed_item"], kind="stable")
    items = got["viewed_item"][order].astype(np.int64)
    if not np.array_equal(items, want["viewed_item"]):
        return None
    diff = np.abs(got["views"][order].astype(np.float64) - want["views"])
    return float(diff.max(initial=0.0))


def warm_up(ctx, cell: BbCell) -> None:
    """Run the query on parameters drawn from the warm-up stream,
    skipping a parameter set already run, until ``quiet`` runs in a row
    compile nothing or ``max_per_query`` sets have been drawn."""
    spec = ctx.traffic["warmup"]
    quiet = draws = 0
    seen = set()
    for items in ctx.rounds("warmup"):
        for item in items:
            draws += 1
            key = tuple(sorted(item["params"].items()))
            if key in seen:
                continue
            seen.add(key)
            before = ctx.compiles
            cell.serve_one(item["params"])
            quiet = quiet + 1 if ctx.compiles == before else 0
        if quiet >= spec["quiet"] or draws >= spec["max_per_query"]:
            return


def setup(ctx, devices) -> BbCell:
    from repro.core.storage_service import ObjectStore
    from repro.serve.query_server import QueryServer
    config = ctx.config
    logical_query(next(ctx.rounds("warmup"))[0]["params"])
    with ctx.phase("datagen"):
        tables = generate_tables(config, ctx.seed)
        store = ObjectStore()
        keys = store_tables(store, config, tables)
    with ctx.phase("server"):
        dep = config["deployment"]
        server = QueryServer(store, worker_budget=dep["worker_budget"],
                             backend=dep["backend"], mode=dep["mode"],
                             result_cache=dep["result_cache"])
        server.register_table("clickstreams", keys["clickstreams"])
        cell = BbCell(ctx, server, tables, keys["item"][0])
    with ctx.phase("warmup"):
        warm_up(ctx, cell)
    return cell
