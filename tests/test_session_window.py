"""TPCx-BB Q3's session window: whole user sessions through the
optimizer's user exchange, the traced kernel against the numpy UDF, both
backends against the benchmark's independent reference, and the spans
and counters the UDF leaves."""
import dataclasses
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.core import spans
from repro.core.storage_service import ObjectStore
from repro.engine import (columnar, compile as engine_compile, datagen,
                          operators, optimizer, queries)
from repro.engine.columnar import ColumnBatch
from repro.engine.coordinator import Coordinator
from repro.engine.plans import (CollectOutput, Pipeline,
                                PlanValidationError, QueryPlan, TableInput)

REPO = Path(__file__).resolve().parents[1]
ORDER = queries.BB_Q3_ORDER
DAY = 86400
WINDOW = 864000


def _reference_module():
    path = REPO / "benchmarks" / "chip" / "configs" / \
        "tpcxbb_sf1_reference.py"
    spec = importlib.util.spec_from_file_location("tpcxbb_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference_module()


def _store_tables(clicks: dict, item: dict, objects: int):
    store = ObjectStore()
    n = len(clicks["wcs_user_sk"])
    bounds = np.linspace(0, n, objects + 1).astype(int)
    keys = {"clickstreams": [], "item": ["tables/item/part-00000"]}
    for p in range(objects):
        key = f"tables/clickstreams/part-{p:05d}"
        store.put(key, columnar.serialize(ColumnBatch(
            {k: v[bounds[p]:bounds[p + 1]] for k, v in clicks.items()})))
        keys["clickstreams"].append(key)
    store.put(keys["item"][0], columnar.serialize(ColumnBatch(item)))
    return store, keys


def _serve(store, keys, backend, params, qid):
    c = Coordinator(store, mode="elastic", backend=backend)
    c.register_table("clickstreams", keys["clickstreams"])
    plan = queries.bb_q3_plan(keys["item"][0], params["target_category"],
                              params["views"], params["seconds"])
    res = c.execute(plan, query_id=qid)
    return {"viewed_item": np.asarray(res.result["viewed_item"]),
            "views": np.asarray(res.result["views"])}


def _as_dict(answer) -> dict:
    return dict(zip(np.asarray(answer["viewed_item"]).tolist(),
                    np.asarray(answer["views"]).tolist()))


@pytest.fixture(scope="module")
def seeded_tables():
    r = np.random.default_rng(2 ** 33 + 16)
    clicks = REF.gen_clickstreams(30000, r, users=400, items=250, days=60)
    item = REF.gen_item(250, r, categories=10)
    return clicks, item


@pytest.mark.parametrize("backend", ["numpy", "jit"])
def test_backends_equal_the_independent_reference(seeded_tables, backend):
    clicks, item = seeded_tables
    store, keys = _store_tables(clicks, item, objects=4)
    sessions = REF.Sessions(clicks)
    for cat in (0, 4, 9):
        params = {"target_category": cat, "views": 5, "seconds": WINDOW}
        got = _serve(store, keys, backend, params, f"sw-{backend}-{cat}")
        want = sessions.bb_q3(item["i_category_id"], params)
        assert sum(want["views"]) > 0
        assert np.array_equal(got["viewed_item"], want["viewed_item"])
        assert np.array_equal(got["views"], want["views"])


# ---------------------------------------------------------------------------
# The kernel against the numpy UDF
# ---------------------------------------------------------------------------

def _batch(rows: list) -> ColumnBatch:
    """Rows of (user, tstamp, item, click type)."""
    a = np.asarray(rows, dtype=np.int64).reshape(-1, 4)
    return ColumnBatch({"wcs_user_sk": a[:, 0],
                        "tstamp": a[:, 1].astype(np.int32),
                        "wcs_item_sk": a[:, 2],
                        "wcs_click_type": a[:, 3].astype(np.int8)})


def _numpy_udf(batch, cats, target, views=5, seconds=WINDOW) -> dict:
    out = operators.udf_reference(
        batch, "clicks_before_purchase", ORDER, item_categories=cats,
        target_category=target, views=views, seconds=seconds)
    counts: dict = {}
    for k in np.asarray(out["viewed_item"]).tolist():
        counts[k] = counts.get(k, 0) + 1
    return counts


def _kernel(batch, cats, target, views=5, seconds=WINDOW) -> dict:
    out = engine_compile._session_window_udf(
        batch, order_by=ORDER, item_categories=cats,
        target_category=target, views=views, seconds=seconds)
    return dict(zip(out["viewed_item"].tolist(), out["n"].tolist()))


def test_kernel_edge_cases():
    """One target-category item (7) bought; items 1-6 viewed."""
    cats = np.zeros(16, np.int8)
    cats[7] = 3
    t = 20 * DAY
    rows = [
        # user 1: a purchase as the first click counts nothing
        [1, t, 7, 2],
        # user 2: six views then a purchase: the oldest does not count;
        # views exactly 864,000 s before count, 864,001 s do not
        [2, t + 100 - WINDOW - 1, 9, 0],
        [2, t + 100 - WINDOW, 1, 0],
        [2, t + 10, 2, 0], [2, t + 20, 3, 0], [2, t + 30, 4, 0],
        [2, t + 40, 5, 0], [2, t + 50, 6, 0], [2, t + 100, 7, 2],
        # user 3: ties in (user, tstamp): a view at the purchase's second
        # sorts before it by item (2 < 7) and counts; one after (item 8)
        # does not; a cart add is no view
        [3, t, 2, 0], [3, t, 7, 2], [3, t, 8, 0], [3, t - 5, 4, 1],
        # user 4: a purchase of a non-target item counts nothing
        [4, t - 1, 5, 0], [4, t, 6, 2],
        # users 5 and 6: a lone view 864,001 s before a purchase does not
        # count, one 864,000 s before does
        [5, t - WINDOW - 1, 10, 0], [5, t, 7, 2],
        [6, t - WINDOW, 11, 0], [6, t, 7, 2],
    ]
    batch = _batch(rows[::-1])                  # arrival order is not kept
    want = {2: 1, 3: 1, 4: 1, 5: 1, 6: 1}      # user 2's last five
    want[2] += 1                                # user 3's tie
    want[11] = 1                                # user 6
    assert _numpy_udf(batch, cats, 3) == want
    assert _kernel(batch, cats, 3) == want
    # The sixth view counts under a window of six views; the 864,001 s
    # one never does, even as the seventh of seven.
    want6 = dict(want)
    want6[1] = 1
    for views in (6, 7):
        assert _numpy_udf(batch, cats, 3, views=views) == want6
        assert _kernel(batch, cats, 3, views=views) == want6


@pytest.mark.parametrize("p_purchase", [0.3, 0.02], ids=["many", "few"])
@pytest.mark.parametrize("seed", range(4))
def test_kernel_equals_numpy_udf_on_random_fragments(seed, p_purchase):
    """Purchases are a third of the clicks, or a few, so that the
    program's room for them takes several sizes."""
    r = np.random.default_rng(seed)
    n = int(r.integers(1, 3000))
    users = int(r.integers(1, 40))
    cats = r.integers(0, 4, 64).astype(np.int8)
    # Few distinct seconds, so (user, tstamp) ties are common.
    rows = np.stack([r.integers(0, users, n),
                     r.integers(0, 40, n) * 100_000,
                     r.integers(0, 64, n),
                     r.choice(3, n, p=[0.9 - p_purchase, 0.1,
                                       p_purchase])], axis=1)
    batch = _batch(rows.tolist())
    for target in range(4):
        assert _kernel(batch, cats, target) == \
            _numpy_udf(batch, cats, target)


def test_kernel_refuses_columns_outside_int32():
    batch = _batch([[2 ** 40, 5, 1, 0], [2 ** 40, 9, 1, 2]])
    out = engine_compile._session_window_udf(
        batch, order_by=ORDER, item_categories=np.zeros(4, np.int8),
        target_category=0)
    assert out is None
    before = engine_compile.FALLBACK_STATS["int32_udf"]
    op = {"op": "udf", "name": "clicks_before_purchase",
          "kwargs": {"item_categories": np.zeros(4, np.int8),
                     "target_category": 0},
          "partition_by": "wcs_user_sk", "order_by": ORDER}
    got = engine_compile.run_pipeline_jit(batch, [op])
    assert engine_compile.FALLBACK_STATS["int32_udf"] == before + 1
    assert got["viewed_item"].tolist() == [1]


# ---------------------------------------------------------------------------
# Whole sessions across objects
# ---------------------------------------------------------------------------

def test_a_session_spanning_objects_counts():
    """User 5 views item 3 in the first object and buys item 7 (target
    category) in the second: the exchange brings both to one call. A
    reducer run on each object alone, as sessions cut at object
    boundaries would be, finds nothing."""
    clicks = {"wcs_user_sk": np.asarray([5, 6, 5, 6], np.int64),
              "wcs_click_date_sk": np.asarray([3, 3, 4, 4], np.int32),
              "wcs_click_time_sk": np.asarray([10, 10, 10, 10], np.int32),
              "wcs_item_sk": np.asarray([3, 1, 7, 2], np.int64),
              "wcs_click_type": np.asarray([0, 0, 2, 0], np.int8)}
    cats = np.zeros(8, np.int8)
    cats[7] = 2
    item = {"i_item_sk": np.arange(8, dtype=np.int64),
            "i_category_id": cats}
    store, keys = _store_tables(clicks, item, objects=2)
    params = {"target_category": 2, "views": 5, "seconds": WINDOW}
    for backend in ("numpy", "jit"):
        got = _serve(store, keys, backend, params, f"span-{backend}")
        assert _as_dict(got) == {3: 1}
    per_object = {}
    for k in keys["clickstreams"]:
        part = columnar.deserialize(store.get(k))
        per_object.update(queries.bb_q3_reference(part, ColumnBatch(item),
                                                  2))
    assert per_object == {}
    assert queries.bb_q3_reference(ColumnBatch(clicks), ColumnBatch(item),
                                   2) == {3: 1}


# ---------------------------------------------------------------------------
# Planner: the exchange, its elision, validation
# ---------------------------------------------------------------------------

def test_the_user_exchange_is_planned():
    plan, report = optimizer.lower(queries.bb_q3_logical(
        "tables/item/part-00000", shuffle_partitions=8))
    scan, sessions, final = plan.pipelines
    assert scan.output.partition_by == "wcs_user_sk"
    assert scan.output.partitions == 8 and scan.fragments is None
    assert sessions.input.from_pipeline == scan.name
    udf = sessions.ops[0]
    assert udf["op"] == "udf" and udf["partition_by"] == "wcs_user_sk"
    assert udf["order_by"] == ORDER
    assert sessions.ops[1]["op"] == "hash_agg"
    assert sessions.output.partition_by == "viewed_item"
    assert any(r.startswith("udf_distribution:") for r in report.rules)


@pytest.mark.parametrize("backend", ["numpy", "jit"])
def test_exchange_elided_for_a_scan_partitioned_by_user(backend):
    store = ObjectStore()
    n = 4
    keys = datagen.load_table_hash_partitioned(
        store, "clickstreams", 20000, "wcs_user_sk", n)
    item_keys = datagen.load_table(store, "item", 200, 1)
    logical = queries.bb_q3_logical(item_keys[0])
    project = logical.root.child.child          # Aggregate > Udf > Project
    project.child = dataclasses.replace(project.child,
                                        partitioned_by=("wcs_user_sk", n))
    plan, report = optimizer.lower(logical)
    assert len(plan.pipelines) == 2
    sessions = plan.pipelines[0]
    assert sessions.input.table == "clickstreams"
    assert sessions.partitioning == {"key": "wcs_user_sk", "fanout": n}
    assert sessions.fragments == n
    assert [op["op"] for op in sessions.ops] == ["project", "udf",
                                                 "hash_agg"]
    assert any("udf clicks_before_purchase exchange on wcs_user_sk "
               "ELIDED" in r for r in report.rules)
    c = Coordinator(store, mode="elastic", backend=backend)
    c.register_table("clickstreams", keys)
    res = c.execute(plan, query_id=f"elided-{backend}")
    clicks = ColumnBatch.concat([columnar.deserialize(store.get(k))
                                 for k in keys])
    item = columnar.deserialize(store.get(item_keys[0]))
    got = dict(zip(res.result["viewed_item"].tolist(),
                   res.result["views"].tolist()))
    assert got == queries.bb_q3_reference(clicks, item)
    assert got


def test_a_udf_without_its_distribution_fails_validation():
    op = {"op": "udf", "name": "clicks_before_purchase", "kwargs": {},
          "partition_by": "wcs_user_sk", "order_by": ORDER}
    plan = QueryPlan("bad", [Pipeline(
        "sessions", TableInput("clickstreams", list(ORDER)), [op],
        CollectOutput())])
    with pytest.raises(PlanValidationError, match="distributed by"):
        plan.validate()
    plan.pipelines[0].fragments = 1       # one fragment holds every user
    plan.validate()


# ---------------------------------------------------------------------------
# Spans and counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "jit"])
def test_every_session_fragment_leaves_a_udf_span(seeded_tables, backend):
    clicks, item = seeded_tables
    store, keys = _store_tables(clicks, item, objects=4)
    before = dict(spans.COUNTERS)
    params = {"target_category": 2, "views": 5, "seconds": WINDOW}
    qid = f"spans-{backend}"
    _serve(store, keys, backend, params, qid)
    records, _ = spans.RING.snapshot()
    by_id = {r.id: r for r in records}
    frags = [r for r in records if r.name == "engine.fragment"
             and r.qid == qid and r.attrs["pipeline"].startswith("udf_")]
    assert len(frags) == 8
    udfs = [r for r in records if r.name == "engine.udf" and r.qid == qid]
    assert len(udfs) == 8
    for r in udfs:
        assert r.attrs["udf"] == "clicks_before_purchase"
        assert r.attrs["rows_in"] > 0 and "rows_out" in r.attrs
        up = by_id[r.parent]
        while up.name != "engine.fragment":
            up = by_id[up.parent]
        assert up in frags
    device = [r for r in records if r.name == "engine.device"
              and r.parent in {u.id for u in udfs}]
    rows = sum(r.attrs["rows_in"] for r in udfs)
    assert rows == len(clicks["wcs_user_sk"])
    grew = {k: spans.COUNTERS[k] - before.get(k, 0)
            for k in spans.COUNTERS}
    scans = [r for r in records if r.name == "engine.fragment"
             and r.qid == qid and r.attrs["pipeline"].startswith("scan_")]
    assert grew["exchange_bytes"] == sum(
        r.attrs.get("exchange_bytes", 0) for r in records
        if r.name == "engine.fragment" and r.qid == qid) > 0
    assert all(r.attrs["exchange_bytes"] > 0 for r in scans)
    if backend == "jit":
        assert len(device) == 8
        assert {r.attrs["kernel"] for r in device} == {"session_window"}
        assert grew["session_window.calls"] == 8
        assert grew["session_window.rows"] == rows
        assert grew["session_window.purchases"] > 0
    else:
        assert device == []
        assert grew.get("session_window.calls", 0) == 0
