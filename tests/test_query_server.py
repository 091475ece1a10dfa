"""Multi-query serving layer: compiled-plan cache keying, admission
control, shared-pool interleaving, and the bitmap-validated result cache.

The serving loop is deterministic in model time (seeded), so throughput
and latency assertions here are exact reproductions, not flaky timing
checks. Parity is asserted against the pure-numpy query references —
serving a query through the shared pool must return byte-for-byte the
same answer as ``Coordinator.execute``.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.elastic_pool import ElasticPool
from repro.core.storage_service import ObjectStore
from repro.core.token_bucket import AdmissionBucket, AdmissionConfig
from repro.engine import columnar, compile as engine_compile
from repro.engine import datagen, plans, queries
from repro.serve.query_server import (QueryRequest, QueryServer,
                                      _TenantAdmitter)

YEAR = datagen.DATE_1994_01_01


# ---------------------------------------------------------------------------
# Canonical plan-shape hash: what shares a trace, what doesn't
# ---------------------------------------------------------------------------

def test_plan_shape_hash_ignores_literals_and_tables():
    a = queries.q12_plan(year_lo=YEAR)
    b = queries.q12_plan(year_lo=YEAR + 180)
    assert plans.plan_shape_hash(a) == plans.plan_shape_hash(b)
    # ... but the full cache key (shape, residue) tells them apart.
    assert plans.plan_cache_key(a) != plans.plan_cache_key(b)
    assert plans.plan_cache_key(a) == plans.plan_cache_key(
        queries.q12_plan(year_lo=YEAR))


def test_plan_shape_hash_sees_fanout_and_structure():
    base = queries.q12_plan(shuffle_partitions=8)
    assert plans.plan_shape_hash(base) != plans.plan_shape_hash(
        queries.q12_plan(shuffle_partitions=4))
    assert plans.plan_shape_hash(base) != plans.plan_shape_hash(
        queries.q6_plan())


def test_plan_shape_hash_survives_json_roundtrip():
    plan = queries.q12_plan()
    clone = plans.QueryPlan.from_json(plan.to_json())
    assert plans.plan_shape_hash(clone) == plans.plan_shape_hash(plan)
    assert plans.plan_cache_key(clone) == plans.plan_cache_key(plan)


def test_plan_shape_hash_stable_across_processes():
    """The hash keys an LRU that outlives any single request — it must
    not depend on process-local state (PYTHONHASHSEED, dict order)."""
    code = ("from repro.engine import plans, queries\n"
            "print(plans.plan_shape_hash(queries.q12_plan()))\n")
    seen = set()
    for seed in ("0", "1", "1234"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH="src" + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr
        seen.add(out.stdout.strip())
    assert len(seen) == 1
    assert seen.pop() == plans.plan_shape_hash(queries.q12_plan())


def test_canonicalize_ops_literal_grammar():
    ops = [{"op": "filter", "expr": ["ge", "x", 5.0]},
           {"op": "filter", "expr": ["in", "m", [1, 2]]}]
    canon, lits = plans.canonicalize_ops(ops)
    assert lits == [5.0, [1, 2]]
    # Same shape for different values, different shape for a longer
    # in-list (its length is part of the compiled trace's shape).
    ops2 = [{"op": "filter", "expr": ["ge", "x", 9.5]},
            {"op": "filter", "expr": ["in", "m", [3, 4]]}]
    assert plans.canonicalize_ops(ops2)[0] == canon
    ops3 = [{"op": "filter", "expr": ["ge", "x", 5.0]},
            {"op": "filter", "expr": ["in", "m", [1, 2, 3]]}]
    assert plans.canonicalize_ops(ops3)[0] != canon


def test_compiled_plan_cache_lru_and_stats():
    cache = engine_compile.CompiledPlanCache(maxsize=2)
    a, b, c = (queries.q12_plan(), queries.q6_plan(), queries.q1_plan())
    ha, hit = cache.lookup(a)
    assert not hit and cache.misses == 1
    _, hit = cache.lookup(a)
    assert hit and cache.hits == 1
    cache.lookup(b)
    cache.lookup(c)                     # evicts a (maxsize=2, LRU)
    assert not cache.contains(ha)
    _, hit = cache.lookup(a)
    assert not hit
    assert cache.stats() == {"hits": 1, "misses": 4, "entries": 2}


# ---------------------------------------------------------------------------
# Admission control: bucket semantics and per-tenant isolation
# ---------------------------------------------------------------------------

def test_admission_bucket_burst_then_refill():
    b = AdmissionBucket(AdmissionConfig(capacity=10.0, refill_per_s=2.0))
    assert b.try_acquire(8, t=0.0)              # burst within capacity
    assert not b.try_acquire(8, t=0.0)          # drained; denial is free
    assert b.tokens_at(0.0) == pytest.approx(2.0)   # consume-on-success only
    assert b.time_until(8, t=0.0) == pytest.approx(3.0)
    assert b.try_acquire(8, t=3.0)              # exactly refilled
    assert b.admitted == 2 and b.denied == 1


def test_admission_bucket_overwide_cost_clamps():
    """A query costing more than the whole capacity admits when the
    bucket is full rather than queueing forever."""
    b = AdmissionBucket(AdmissionConfig(capacity=4.0, refill_per_s=1.0))
    assert b.time_until(100, t=0.0) == 0.0
    assert b.try_acquire(100, t=0.0)
    assert b.tokens_at(0.0) == pytest.approx(0.0)


def test_tenant_admitter_isolation():
    """One tenant draining its bucket cannot starve another: buckets are
    per-tenant, never shared."""
    adm = _TenantAdmitter(AdmissionConfig(capacity=6.0, refill_per_s=1.0))
    greedy, other = adm.bucket("greedy"), adm.bucket("other")
    while greedy.try_acquire(2, t=0.0):
        pass
    assert greedy.denied >= 1
    assert other.try_acquire(6, t=0.0)          # full despite the neighbor
    assert other.denied == 0


# ---------------------------------------------------------------------------
# Elastic pool: scale-up and scale-down under a fragment burst
# ---------------------------------------------------------------------------

def test_elastic_pool_scale_up_down_stats():
    pool = ElasticPool(rng_seed=0)
    burst = pool.acquire(16, t=0.0)
    pool.release(burst, t=1.0, busy_s=1.0)
    assert pool.stats["peak_warm"] >= 16        # scale-up high-water mark
    assert pool.stats["cold_starts"] == 16
    assert pool.stats["expired"] == 0
    # A follow-up burst while warm reuses the fleet...
    again = pool.acquire(8, t=2.0)
    pool.release(again, t=3.0, busy_s=1.0)
    assert pool.stats["warm_starts"] == 8
    # ... and idling past the sandbox lifetime scales it back down.
    idle_t = 3.0 + pool.limits.idle_lifetime_s + 1.0
    pool.acquire(1, t=idle_t)
    assert pool.stats["expired"] == 16
    assert pool.warm_count() == 0


# ---------------------------------------------------------------------------
# Serving loop end to end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serving_store():
    store = ObjectStore()
    tables = {
        "lineitem": datagen.load_table(store, "lineitem", 12000, 6),
        "orders": datagen.load_table(store, "orders", 3000, 3),
    }
    return store, tables


def _make_server(store, tables, **kw):
    srv = QueryServer(store, worker_budget=8, rng_seed=0, **kw)
    for t, keys in tables.items():
        srv.register_table(t, keys)
    return srv


def _full(store, keys):
    from repro.engine.columnar import ColumnBatch
    return ColumnBatch.concat(
        [columnar.deserialize(store.get(k)) for k in keys])


def test_serving_parity_and_plan_cache_hits(serving_store):
    """Four same-shape Q12 variants through the shared pool: every
    result matches the numpy reference for ITS literals, the first query
    misses the fresh plan cache, every follower hits."""
    store, tables = serving_store
    engine_compile.PLAN_CACHE.clear()
    srv = _make_server(store, tables, result_cache=False)
    reqs = [QueryRequest(queries.q12_logical(year_lo=YEAR + 60 * i),
                         tenant=f"tenant{i % 2}") for i in range(4)]
    report = srv.serve(reqs)

    li = _full(store, tables["lineitem"])
    od = _full(store, tables["orders"])
    for i, served in enumerate(report.queries):
        ref = queries.q12_reference(li, od, year_lo=YEAR + 60 * i)
        got = served.result.result
        assert got.num_rows == ref.num_rows
        order = np.argsort(np.asarray(got["l_shipmode"]))
        for col in ("high_line_count", "low_line_count"):
            np.testing.assert_allclose(
                np.asarray(got[col])[order], np.asarray(ref[col]),
                rtol=1e-6)
    assert report.plan_cache_misses == 1
    assert report.plan_cache_hits == 3
    assert report.plan_cache_hit_rate == pytest.approx(0.75)
    assert not report.queries[0].plan_cache_hit
    assert all(s.plan_cache_hit for s in report.queries[1:])
    # Every served query carries the per-tier exchange cost breakdown:
    # q12's combine rides the KV tier, the bulk row shuffles stay on the
    # object store, and the split sums back to the storage bill.
    for served in report.queries:
        res = served.result
        assert set(res.exchange_cost_usd) == {"object", "kv"}
        assert res.exchange_cost_usd["object"] > 0.0
        assert res.exchange_cost_usd["kv"] > 0.0
        assert sum(res.exchange_cost_usd.values()) == \
            pytest.approx(res.storage_cost_usd)
    # Both tenants served; nobody denied at the default budget.
    assert set(report.admission) == {"tenant0", "tenant1"}
    assert all(v["admitted"] >= 1 for v in report.admission.values())


def test_interleaved_beats_serial_at_fixed_budget(serving_store):
    store, tables = serving_store
    reqs = lambda: [QueryRequest(queries.q12_logical(year_lo=YEAR + 45 * i))
                    for i in range(4)]
    serial = _make_server(store, tables, result_cache=False).serve(
        reqs(), interleave=False)
    inter = _make_server(store, tables, result_cache=False).serve(reqs())
    assert inter.makespan_s < serial.makespan_s
    assert inter.throughput_qps > serial.throughput_qps


def test_result_cache_hit_and_invalidation(serving_store):
    store, tables = serving_store
    srv = _make_server(store, tables)
    req = lambda: QueryRequest(queries.q12_logical(year_lo=YEAR))

    first = srv.serve([req()])
    assert first.result_cache_hits == 0
    second = srv.serve([req()])
    assert second.result_cache_hits == 1
    assert second.queries[0].result_cache_hit
    assert second.queries[0].latency_s == 0.0
    assert second.queries[0].result.result.num_rows == \
        first.queries[0].result.result.num_rows

    # Overwriting a scanned table object bumps its etag -> invalidation.
    k = tables["lineitem"][0]
    store.put(k, store.get(k))
    third = srv.serve([req()])
    assert third.result_cache_hits == 0
    assert srv.result_cache.invalidated == 1
    # The re-run repopulates the cache against the new etag.
    fourth = srv.serve([req()])
    assert fourth.result_cache_hits == 1


def test_result_cache_bitmap_validation(serving_store):
    """Deleting a shuffle partition that a writer's bitmap records as
    WRITTEN invalidates the entry — the cached result can no longer be
    audited against its intermediates. Partitions the bitmaps record as
    skipped-empty were never resident, so they don't invalidate."""
    from repro.engine import worker as worker_mod

    store, tables = serving_store
    srv = _make_server(store, tables)
    res = srv.serve([QueryRequest(queries.q12_logical(year_lo=YEAR + 7))])
    assert res.result_cache_hits == 0
    (entry,) = [e for e in srv.result_cache._entries.values()
                if e["query_id"] == res.queries[0].query_id]
    qid = entry["query_id"]
    set_keys = []
    for (_, pipeline, writer), (att, bm) in entry["bitmaps"].items():
        p = 0
        while bm >> p:
            if (bm >> p) & 1:
                set_keys.append(
                    (pipeline,
                     worker_mod.shuffle_key(qid, pipeline, writer, p, att)))
            p += 1
    assert set_keys, "q12 must produce shuffle partitions"
    # Shuffles may ride either exchange tier; delete the partition from
    # the store that actually holds it so the etag probe sees it gone.
    pipeline, key = set_keys[0]
    tier = entry["tiers"].get(pipeline, "object")
    owner = srv.coordinator.kv_store if tier == "kv" else store
    owner.delete(key)
    miss = srv.serve([QueryRequest(queries.q12_logical(year_lo=YEAR + 7))])
    assert miss.result_cache_hits == 0
    assert srv.result_cache.invalidated >= 1


def test_serving_under_tight_admission_still_completes(serving_store):
    """With a bucket far smaller than the burst, queries queue rather
    than fail: every query completes, later ones wait for refill."""
    store, tables = serving_store
    srv = _make_server(
        store, tables, result_cache=False,
        admission=AdmissionConfig(capacity=12.0, refill_per_s=4.0))
    reqs = [QueryRequest(queries.q12_logical(year_lo=YEAR + 30 * i))
            for i in range(3)]
    report = srv.serve(reqs)
    assert len(report.queries) == 3
    assert all(s.result.result.num_rows > 0 for s in report.queries)
    adm = report.admission["default"]
    assert adm["admitted"] == 3 and adm["denied"] >= 1
    # Queued queries are admitted strictly later than they arrived.
    assert any(s.admit_t > s.submit_t for s in report.queries)


def test_bench_profile_section_accessor(tmp_path):
    from repro.core import bench_profile

    p = tmp_path / "bench.json"
    p.write_text(json.dumps({"concurrent_serving": {"speedup": 2.0}}))
    assert bench_profile.section("concurrent_serving", path=p) == \
        {"speedup": 2.0}
    assert bench_profile.section("missing", path=p) == {}


def test_bench_profile_stale_section_warns_once(tmp_path):
    """A profile file that exists but lacks the requested section is stale
    (the caller's benchmark was added after the last run): warn once per
    section name, return the documented fallback. A missing file stays
    silent — fresh checkouts have no BENCH_engine.json at all."""
    import warnings

    from repro.core import bench_profile

    p = tmp_path / "bench.json"
    p.write_text(json.dumps({"planning": {"x": 1.0}}))
    bench_profile.clear_cache()
    fb = {"object_exchange_bytes_per_s": 1.0}
    with pytest.warns(RuntimeWarning, match="no 'tiered_exchange' section"):
        assert bench_profile.section("tiered_exchange", path=p,
                                     fallback=fb) == fb
    # Second probe for the same section is silent (warn-once).
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert bench_profile.section("tiered_exchange", path=p) == {}
        # Missing file: silent, regardless of section.
        assert bench_profile.section(
            "anything", path=tmp_path / "absent.json") == {}
    bench_profile.clear_cache()


def test_server_fixes_heap_thresholds(monkeypatch):
    """Building a server fixes the C library's heap thresholds, after
    which a 20 MiB block comes from the heap, not a fresh mapping."""
    import ctypes

    from repro.core import host_memory
    calls = []
    monkeypatch.setattr(host_memory, "steady_heap",
                        lambda: calls.append(1) or True)
    QueryServer(ObjectStore(), result_cache=False)
    assert calls == [1]
    monkeypatch.undo()

    class MallInfo2(ctypes.Structure):
        _fields_ = [(f, ctypes.c_size_t) for f in (
            "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
            "fsmblks", "uordblks", "fordblks", "keepcost")]

    libc = ctypes.CDLL(None)
    libc.mallinfo2.restype = MallInfo2
    assert host_memory.steady_heap()
    before = libc.mallinfo2().hblkhd
    block = np.ones(20 << 20, dtype=np.uint8)
    assert libc.mallinfo2().hblkhd - before < 20 << 20
    assert int(block.sum()) == 20 << 20
