"""Query engine end-to-end: results match pure-numpy references in both
deployment modes; stage scheduling, cost accounting, burst-aware planning.

The coordinator runs the compiled jit backend by default, whose float
contract is aggregate parity at rtol=1e-6 against float64 (pairwise f32
accumulation; see docs/BACKENDS.md) — float comparisons here use that
tolerance. ``test_numpy_reference_backend_exact`` keeps the rel=1e-9
check alive on the explicit numpy semantic-reference backend."""
import numpy as np
import pytest

from repro.core.storage_service import ObjectStore
from repro.engine import columnar, datagen, queries
from repro.engine.columnar import ColumnBatch
from repro.engine.coordinator import Coordinator
from repro.engine.plans import QueryPlan


@pytest.fixture(scope="module")
def loaded_store():
    store = ObjectStore()
    keys = {
        "lineitem": datagen.load_table(store, "lineitem", 20000, 8),
        "orders": datagen.load_table(store, "orders", 5000, 4),
        "clickstreams": datagen.load_table(store, "clickstreams", 20000, 6),
        "item": datagen.load_table(store, "item", 200, 1),
    }
    return store, keys


def _full(store, keys):
    return ColumnBatch.concat(
        [columnar.deserialize(store.get(k)) for k in keys])


@pytest.fixture(scope="module", params=["elastic", "provisioned"])
def coordinator(request, loaded_store):
    store, keys = loaded_store
    c = Coordinator(store, mode=request.param)
    for t in ("lineitem", "orders", "clickstreams"):
        c.register_table(t, keys[t])
    return c


def test_q6(coordinator, loaded_store):
    store, keys = loaded_store
    res = coordinator.execute(queries.q6_plan(),
                              query_id=f"q6-{coordinator.mode}-t")
    ref = queries.q6_reference(_full(store, keys["lineitem"]))
    assert float(res.result["revenue"][0]) == pytest.approx(ref, rel=1e-6)
    assert res.runtime_s > 0
    assert res.faas_cost_usd > 0


def test_q1(coordinator, loaded_store):
    store, keys = loaded_store
    res = coordinator.execute(queries.q1_plan(),
                              query_id=f"q1-{coordinator.mode}-t")
    ref = queries.q1_reference(_full(store, keys["lineitem"]))
    assert res.result.num_rows == ref.num_rows == 6
    got = sorted(zip(res.result["l_returnflag"].tolist(),
                     res.result["l_linestatus"].tolist(),
                     res.result["sum_charge"].tolist()))
    want = sorted(zip(ref["l_returnflag"].tolist(),
                      ref["l_linestatus"].tolist(),
                      ref["sum_charge"].tolist()))
    for g, w in zip(got, want):
        assert g[:2] == w[:2]
        assert g[2] == pytest.approx(w[2], rel=1e-6)


def test_q12(coordinator, loaded_store):
    store, keys = loaded_store
    res = coordinator.execute(queries.q12_plan(),
                              query_id=f"q12-{coordinator.mode}-t")
    ref = queries.q12_reference(_full(store, keys["lineitem"]),
                                _full(store, keys["orders"]))
    got = dict(zip(res.result["l_shipmode"].tolist(),
                   zip(res.result["high_line_count"].tolist(),
                       res.result["low_line_count"].tolist())))
    want = dict(zip(ref["l_shipmode"].tolist(),
                    zip(ref["high_line_count"].tolist(),
                        ref["low_line_count"].tolist())))
    assert got == want


def test_bb_q3_totals(coordinator, loaded_store):
    """Whole user sessions: the optimizer's exchange on wcs_user_sk puts
    every user's clicks, from all six objects, in one UDF call, so the
    answer is the whole-table reference's."""
    store, keys = loaded_store
    plan = queries.bb_q3_plan(keys["item"][0])
    res = coordinator.execute(plan, query_id=f"bbq3-{coordinator.mode}-t")
    item = columnar.deserialize(store.get(keys["item"][0]))
    ref = queries.bb_q3_reference(_full(store, keys["clickstreams"]), item)
    got = dict(zip(res.result["viewed_item"].tolist(),
                   res.result["views"].tolist()))
    assert got == ref
    assert int(res.result["views"].sum()) == sum(ref.values())


def test_plan_json_roundtrip():
    plan = queries.q12_plan()
    text = plan.to_json()
    back = QueryPlan.from_json(text)
    assert [p.name for p in back.pipelines] == \
        [p.name for p in plan.pipelines]
    assert back.pipelines[2].join == plan.pipelines[2].join


def test_numpy_reference_backend_exact(loaded_store):
    """The demoted numpy backend stays the float64 semantic reference:
    exact (rel=1e-9) agreement with the pure-numpy query references."""
    store, keys = loaded_store
    c = Coordinator(store, mode="elastic", backend="numpy")
    for t in ("lineitem", "orders"):
        c.register_table(t, keys[t])
    res = c.execute(queries.q6_plan(), query_id="q6-npref")
    ref = queries.q6_reference(_full(store, keys["lineitem"]))
    assert float(res.result["revenue"][0]) == pytest.approx(ref, rel=1e-9)
    res1 = c.execute(queries.q1_plan(), query_id="q1-npref")
    ref1 = queries.q1_reference(_full(store, keys["lineitem"]))
    got = sorted(zip(res1.result["l_returnflag"].tolist(),
                     res1.result["l_linestatus"].tolist(),
                     res1.result["sum_charge"].tolist()))
    want = sorted(zip(ref1["l_returnflag"].tolist(),
                      ref1["l_linestatus"].tolist(),
                      ref1["sum_charge"].tolist()))
    for g, w in zip(got, want):
        assert g[:2] == w[:2]
        assert g[2] == pytest.approx(w[2], rel=1e-9)


def test_faas_vs_iaas_same_result(loaded_store):
    store, keys = loaded_store
    results = {}
    for mode in ("elastic", "provisioned"):
        c = Coordinator(store, mode=mode)
        c.register_table("lineitem", keys["lineitem"])
        res = c.execute(queries.q6_plan(), query_id=f"q6-cmp-{mode}")
        results[mode] = float(res.result["revenue"][0])
    assert results["elastic"] == pytest.approx(results["provisioned"])


def test_stage_metrics_and_peak_workers(coordinator, loaded_store):
    res = coordinator.execute(queries.q12_plan(),
                              query_id=f"q12m-{coordinator.mode}")
    assert set(res.stage_metrics) == {"scan_lineitem", "scan_orders",
                                      "join_agg", "final_agg"}
    assert res.peak_workers >= 1
    assert res.request_stats.reads > 0 and res.request_stats.writes > 0


def test_burst_aware_fewer_or_equal_runtime(loaded_store):
    """Burst-aware partition assignment must not be slower (Fig 14)."""
    store, keys = loaded_store
    runtimes = {}
    for aware in (True, False):
        c = Coordinator(store, mode="elastic", burst_aware=aware)
        c.register_table("lineitem", keys["lineitem"])
        res = c.execute(queries.q6_plan(), query_id=f"q6-burst-{aware}")
        runtimes[aware] = res.runtime_s
    assert runtimes[True] <= runtimes[False] * 1.2
