"""Out-of-core execution: differential spill parity + budget properties.

The load-bearing contract of ROADMAP item 4 is *bit identity*: a fragment
executed under a forcing memory budget — morsel streaming, accumulator
spill rounds, a spilled join build — must produce byte-for-byte the same
results as the unbudgeted in-memory path on the same backend. The
differential harness here runs every existing parity query (the four
paper queries plus the PR 5/7/8 end-to-end shapes) twice, unlimited vs a
budget small enough to force >= 2 spill rounds (asserted through the
``engine.spill.SPILL_STATS`` spy), on both backends.

Property tests (hypothesis, optional via ``hypo_compat``) pin the three
spill primitives: partition-accumulator contents match the single-shot
radix partitioner, a spilled (mmap-backed) join build matches
``op_hash_join`` exactly, and ``core.memory`` accounting invariants hold
under arbitrary reserve/release sequences.
"""
import numpy as np
import pytest

from hypo_compat import HAS_HYPOTHESIS, given, settings, st
from repro.core import memory as core_memory
from repro.core.storage_service import ObjectStore
from repro.engine import columnar, datagen, operators, optimizer, queries
from repro.engine import spill, worker
from repro.engine.adaptive import AdaptiveCoordinator, AdaptivePolicy
from repro.engine.columnar import ColumnBatch
from repro.engine.coordinator import Coordinator
from repro.engine.logical import col, count_, max_, scan, sum_

BACKENDS = ["jit", "numpy"]

# A budget small enough that every parity query's accumulators flush
# through multiple spill rounds on BOTH backends (the numpy backend
# streams selective prefixes, so its accumulated bytes are far smaller
# than the jit backend's raw morsels — the cap must force rounds even
# then), with morsels a few hundred rows so fragments see many of them.
FORCING_CAP = 512.0
FORCING_MORSEL = 128


# ---------------------------------------------------------------------------
# Shared data
# ---------------------------------------------------------------------------

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


def _assert_identical(unlimited, capped):
    assert sorted(unlimited.keys()) == sorted(capped.keys())
    assert unlimited.num_rows == capped.num_rows
    for c in unlimited.keys():
        a, b = np.asarray(unlimited[c]), np.asarray(capped[c])
        assert a.dtype == b.dtype, c
        assert np.array_equal(a, b), c


def _run_differential(make_coordinator, plan_factory, qid, backend,
                      execute=None):
    """Run the same physical plan unlimited vs spill-forced; return both
    results after asserting the forcing run actually spilled."""
    results = {}
    for tag, kw in (("unlimited", {}),
                    ("capped", {"memory_budget": FORCING_CAP,
                                "morsel_rows": FORCING_MORSEL})):
        coord = make_coordinator(backend=backend, **kw)
        spill.reset_stats()
        run = execute or (lambda c, p, q: c.execute(p, query_id=q))
        results[tag] = run(coord, plan_factory(), f"{qid}-{tag}-{backend}")
        if tag == "capped":
            assert spill.SPILL_STATS["spill_bytes"] > 0, qid
            assert spill.SPILL_STATS["spill_rounds"] >= 2, qid
            assert results[tag].spill_bytes > 0          # surfaced e2e
            assert results[tag].mem_peak_bytes > 0
    _assert_identical(results["unlimited"].result, results["capped"].result)
    return results


# ---------------------------------------------------------------------------
# Differential spill parity: the four paper queries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("query", ["q1", "q6", "q12", "bb_q3"])
def test_paper_query_spill_parity(query, backend, loaded_store):
    store, keys = loaded_store

    def make_coordinator(**kw):
        c = Coordinator(store, mode="elastic", **kw)
        for t in ("lineitem", "orders", "clickstreams"):
            c.register_table(t, keys[t])
        return c

    if query == "bb_q3":
        def plan_factory():
            return queries.bb_q3_plan(keys["item"][0])
    else:
        plan_factory = getattr(queries, f"{query}_plan")
    _run_differential(make_coordinator, plan_factory, f"ooc-{query}",
                      backend)


# ---------------------------------------------------------------------------
# Differential spill parity: PR 5/7/8 end-to-end shapes
# ---------------------------------------------------------------------------

def _elision_query(n: int = 8):
    """PR 5's fully-elided shape: hash-partitioned base tables + agg on
    the join key collapse to ONE pipeline with zero shuffles — the
    out-of-core path must hold on direct table-partition reads and a
    fragment-local (collapsed) trailing aggregate."""
    return (
        scan("lineitem", ["l_orderkey", "l_extendedprice", "l_discount"],
             partitioned_by=("l_orderkey", n))
        .join(scan("orders", ["o_orderkey", "o_totalprice"],
                   partitioned_by=("o_orderkey", n)),
              on=("l_orderkey", "o_orderkey"))
        .select("l_orderkey",
                (col("l_extendedprice") * (1 - col("l_discount")))
                .alias("revenue"), "o_totalprice")
        .group_by("l_orderkey")
        .agg(sum_("revenue").alias("revenue"),
             count_("revenue").alias("n_lines"),
             max_("o_totalprice").alias("o_total"))
        .collect("ooc_elision", shuffle_partitions=n))


@pytest.fixture(scope="module")
def partitioned_store():
    n = 8
    store = ObjectStore()
    keys = {
        "lineitem": datagen.load_table_hash_partitioned(
            store, "lineitem", 20000, "l_orderkey", n),
        "orders": datagen.load_table_hash_partitioned(
            store, "orders", 5000, "o_orderkey", n),
    }
    return store, keys, n


@pytest.mark.parametrize("backend", BACKENDS)
def test_elision_shape_spill_parity(backend, partitioned_store):
    store, keys, n = partitioned_store

    def make_coordinator(**kw):
        c = Coordinator(store, mode="elastic", **kw)
        for t, k in keys.items():
            c.register_table(t, k)
        return c

    def plan_factory():
        return optimizer.plan(_elision_query(n), backend=backend)

    _run_differential(make_coordinator, plan_factory, "ooc-elision",
                      backend)


def _tiered_query(n: int = 4):
    """PR 7's shape: bulk join shuffles + a tiny combine, forced onto the
    KV exchange tier so the out-of-core path is exercised on KV-tier
    shuffle reads and writes too."""
    return (
        scan("lineitem", ["l_orderkey", "l_shipmode", "l_extendedprice",
                          "l_discount"])
        .join(scan("orders", ["o_orderkey", "o_orderpriority"]),
              on=("l_orderkey", "o_orderkey"))
        .select("l_shipmode",
                (col("l_extendedprice") * (1 - col("l_discount")))
                .alias("revenue"), "o_orderpriority")
        .group_by("l_shipmode")
        .agg(sum_("revenue").alias("revenue"),
             count_("revenue").alias("n_lines"))
        .collect("ooc_tiered", shuffle_partitions=n))


@pytest.mark.parametrize("backend", BACKENDS)
def test_tiered_shape_spill_parity(backend, loaded_store):
    store, keys = loaded_store

    def make_coordinator(**kw):
        c = Coordinator(store, mode="provisioned", **kw)
        for t in ("lineitem", "orders"):
            c.register_table(t, keys[t])
        return c

    def plan_factory():
        return optimizer.plan(_tiered_query(), backend=backend,
                              exchange_tiers="kv")

    _run_differential(make_coordinator, plan_factory, "ooc-tiered",
                      backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_adaptive_shape_spill_parity(backend, loaded_store):
    """PR 8's shape: stage-at-a-time execution with boundary revisions.
    Fan-out replanning is pinned off because the capped coordinator
    would legitimately re-derive a HIGHER fan-out from its own memory
    term — a different plan whose float association differs in bits;
    every other adaptive decision must preserve parity."""
    store, keys = loaded_store
    policy = AdaptivePolicy(replan_fanout=False)

    def make_coordinator(**kw):
        c = AdaptiveCoordinator(store, policy=policy, mode="provisioned",
                                **kw)
        for t in ("lineitem", "orders"):
            c.register_table(t, keys[t])
        return c

    def plan_factory():
        return optimizer.plan(_tiered_query(), backend=backend)

    _run_differential(make_coordinator, plan_factory, "ooc-adaptive",
                      backend)


# ---------------------------------------------------------------------------
# Worker-level differential: forced build spill, byte-identical shuffle
# ---------------------------------------------------------------------------

def _join_store(rows=6000, build_rows=1500, objects=4):
    r = np.random.default_rng(11)
    probe = ColumnBatch({
        "l_orderkey": r.integers(1, build_rows + 1, size=rows,
                                 dtype=np.int64),
        "l_shipmode": r.integers(0, 7, size=rows, dtype=np.int8),
    })
    build = ColumnBatch({
        "o_orderkey": r.permutation(np.arange(1, build_rows + 1)
                                    ).astype(np.int64),
        "o_orderpriority": r.integers(0, 5, size=build_rows,
                                      dtype=np.int8),
    })
    store = ObjectStore()
    keys, keys2 = [], []
    step = rows // objects
    for i in range(objects):
        b = ColumnBatch({k: np.asarray(v)[i * step:(i + 1) * step]
                         for k, v in probe.items()})
        store.put(f"t/probe/{i}", columnar.serialize_frame(b))
        keys.append(f"t/probe/{i}")
    store.put("t/build/0", columnar.serialize_frame(build))
    keys2.append("t/build/0")
    return store, keys, keys2


@pytest.mark.parametrize("backend", BACKENDS)
def test_worker_fragment_build_spill_byte_identity(backend):
    store, keys, keys2 = _join_store()
    ops = [
        {"op": "hash_join", "left_key": "l_orderkey",
         "right_key": "o_orderkey"},
        {"op": "filter", "expr": ["in", "l_shipmode", [2, 5]]},
        {"op": "project", "columns": [
            "l_orderkey", "l_shipmode",
            ["pri", ["case_in", "o_orderpriority", [0, 1]]]]},
    ]

    def run(tag, budget):
        spec = worker.FragmentSpec(
            query_id=f"ooc-frag-{tag}-{backend}", pipeline="p", fragment=0,
            read_keys=keys, read_keys2=keys2, columns=None, ops=ops,
            output={"type": "shuffle", "partition_by": "l_orderkey",
                    "partitions": 8},
            backend=backend, missing_ok2=False, memory_budget=budget,
            morsel_rows=None if budget is None else 256)
        spill.reset_stats()
        metrics = worker.execute_fragment(store, spec)
        return metrics, dict(spill.SPILL_STATS)

    base_m, _ = run("base", None)
    cap_m, stats = run("cap", 4096.0)
    # The ~13 KiB build cannot fit a 4 KiB cap: it must demote to a
    # spilled mmap frame, and the partition buffers must flush rounds.
    assert stats["spilled_builds"] == 1
    assert stats["spill_rounds"] >= 2
    assert cap_m.spill_bytes > 0
    assert cap_m.mem_cap_bytes == 4096
    assert cap_m.rows_in == base_m.rows_in
    assert cap_m.rows_out == base_m.rows_out
    base_keys = sorted(store.list(f"shuffle/ooc-frag-base-{backend}/"))
    cap_keys = sorted(store.list(f"shuffle/ooc-frag-cap-{backend}/"))
    assert [k.rsplit("/", 1)[-1] for k in base_keys] == \
        [k.rsplit("/", 1)[-1] for k in cap_keys]
    for bk, ck in zip(base_keys, cap_keys):
        assert store.get(bk) == store.get(ck)


def test_capped_peak_stays_bounded():
    """The accounting teeth: a capped streamable fragment's peak stays
    within cap + one emitted partition (chunked emission, not a full
    reorder), far below the unbudgeted working set."""
    store, keys, keys2 = _join_store(rows=20000, build_rows=200)
    ops = [{"op": "filter", "expr": ["in", "l_shipmode", [0, 1, 2, 3]]}]

    def run(tag, budget):
        spec = worker.FragmentSpec(
            query_id=f"ooc-peak-{tag}", pipeline="p", fragment=0,
            read_keys=keys, read_keys2=[], columns=None, ops=ops,
            output={"type": "shuffle", "partition_by": "l_orderkey",
                    "partitions": 16},
            backend="numpy", memory_budget=budget,
            morsel_rows=None if budget == float("inf") else 512)
        return worker.execute_fragment(store, spec)

    acct = run("acct", float("inf"))
    cap = 8 * 1024
    capped = run("cap", float(cap))
    assert capped.spill_bytes > 0
    # One partition of the ~101 KiB filtered output is ~6.3 KiB: peak
    # must stay within cap + one partition + one morsel, not the full
    # accumulated output the unbudgeted run holds.
    assert capped.mem_peak_bytes < acct.mem_peak_bytes / 2
    assert capped.mem_peak_bytes <= cap + acct.mem_peak_bytes // 4
    assert capped.mem_overcommit_bytes >= 0


# ---------------------------------------------------------------------------
# Primitive parity (plain unit tests, always run)
# ---------------------------------------------------------------------------

def _rand_batch(rows, seed=0):
    r = np.random.default_rng(seed)
    return ColumnBatch({
        "k": r.integers(0, 97, size=rows, dtype=np.int64),
        "v": r.standard_normal(rows),
        "w": r.integers(-5, 5, size=rows, dtype=np.int32),
    })


def test_radix_partition_iter_matches_single_shot():
    batch = _rand_batch(5000, seed=1)
    parts = operators.radix_partition(batch, "k", 7)
    assert len(parts) == 7
    for p, (pid, b) in enumerate(operators.radix_partition_iter(batch,
                                                                "k", 7)):
        assert pid == p
        _assert_identical(parts[p], b)
        assert np.all(np.asarray(b["k"]) % 7 == p)
    # Stability: concat of per-morsel partitions == partition of concat.
    morsels = [batch.select(np.arange(batch.num_rows) // 1000 == i)
               for i in range(5)]
    for p in range(7):
        merged = ColumnBatch.concat(
            [operators.radix_partition(m, "k", 7)[p] for m in morsels])
        _assert_identical(parts[p], merged)


def test_spill_file_roundtrip_exact():
    sf = spill.SpillFile()
    batches = [_rand_batch(100, seed=i) for i in range(4)]
    locs = [sf.append(b) for b in batches]
    for b, (off, length) in zip(batches, locs):
        _assert_identical(b, sf.read(off, length))
    # Projection pushdown on read-back touches only requested buffers.
    one = sf.read(*locs[2], columns=["v"])
    assert list(one.keys()) == ["v"]
    assert np.array_equal(one["v"], batches[2]["v"])


def test_spilled_build_join_exact():
    r = np.random.default_rng(3)
    build = ColumnBatch({
        "bk": np.repeat(np.arange(50, dtype=np.int64), 2),  # dup keys
        "bv": r.standard_normal(100),
    })
    probe = ColumnBatch({
        "pk": r.integers(0, 60, size=400, dtype=np.int64),
        "pv": r.standard_normal(400),
    })
    mem = operators.op_hash_join(probe, build, "pk", "bk")
    spilled = operators.op_hash_join(probe, spill.spill_build(build),
                                     "pk", "bk")
    _assert_identical(mem, spilled)


def test_batch_accumulator_spills_and_preserves_order():
    budget = core_memory.MemoryBudget(4096)
    acc = spill.BatchAccumulator(budget.grant("acc"))
    batches = [_rand_batch(80, seed=i) for i in range(12)]
    spill.reset_stats()
    for b in batches:
        acc.add(b)
    assert spill.SPILL_STATS["spill_rounds"] >= 2
    _assert_identical(ColumnBatch.concat(batches), acc.finalize())
    # The materialized concat was force-charged: overcommit is recorded,
    # not hidden.
    assert budget.overcommit_bytes > 0


def test_partition_accumulator_matches_radix():
    budget = core_memory.MemoryBudget(2048)
    acc = spill.PartitionAccumulator(5, budget.grant("acc"))
    batches = [_rand_batch(120, seed=10 + i) for i in range(8)]
    spill.reset_stats()
    for b in batches:
        for p, pb in enumerate(operators.radix_partition(b, "k", 5)):
            acc.add(p, pb)
    assert spill.SPILL_STATS["spill_rounds"] >= 2
    whole = operators.radix_partition(ColumnBatch.concat(batches), "k", 5)
    for p in range(5):
        got = acc.take(p)
        if whole[p].num_rows == 0:
            assert got.num_rows == 0
        else:
            _assert_identical(whole[p], got)
    assert budget.reserved_bytes == 0     # every take released its chunks


def test_budget_accounting_basics():
    budget = core_memory.MemoryBudget(1000)
    g1, g2 = budget.grant("a"), budget.grant("b", cap_bytes=100)
    with pytest.raises(ValueError):
        budget.grant("a")
    assert g1.try_reserve(800)
    assert not g1.try_reserve(300)         # worker cap refuses
    assert not g2.try_reserve(150)         # per-grant cap refuses
    assert g2.try_reserve(100)
    assert budget.reserved_bytes == 900
    assert budget.peak_bytes == 900 <= budget.cap_bytes
    with pytest.raises(core_memory.MemoryBudgetExceeded):
        g1.reserve(500)
    g1.reserve(500, force=True)            # barrier escape hatch
    assert budget.overcommit_bytes == 400
    g1.release_all()
    g2.release(100)
    assert budget.reserved_bytes == 0
    with pytest.raises(ValueError):
        g2.release(1)                      # double release fails loudly


# ---------------------------------------------------------------------------
# Hypothesis properties
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:
    _rows = st.integers(min_value=0, max_value=400)
    _parts = st.integers(min_value=1, max_value=9)
    _cap = st.integers(min_value=256, max_value=1 << 14)
    _seed = st.integers(min_value=0, max_value=2 ** 31)


@given(rows=_rows if HAS_HYPOTHESIS else None,
       parts=_parts if HAS_HYPOTHESIS else None,
       cap=_cap if HAS_HYPOTHESIS else None,
       seed=_seed if HAS_HYPOTHESIS else None)
@settings(max_examples=40, deadline=None)
def test_prop_spilled_partition_contents(rows, parts, cap, seed):
    """Spilled radix partition contents == in-memory partition contents,
    for any morsel split, partition count and (possibly forcing) cap."""
    batch = _rand_batch(rows, seed=seed)
    budget = core_memory.MemoryBudget(cap)
    acc = spill.PartitionAccumulator(parts, budget.grant("acc"))
    r = np.random.default_rng(seed)
    cuts = np.sort(r.integers(0, rows + 1, size=3)) if rows else []
    lo = 0
    for hi in list(cuts) + [rows]:
        m = ColumnBatch({k: np.asarray(v)[lo:hi]
                         for k, v in batch.items()})
        for p, pb in enumerate(operators.radix_partition(m, "k", parts)):
            acc.add(p, pb)
        lo = hi
    whole = operators.radix_partition(batch, "k", parts)
    for p in range(parts):
        got = acc.take(p)
        if whole[p].num_rows == 0:
            # A never-fed partition materializes as the columnless empty
            # batch — the shuffle writer skips it either way.
            assert got.num_rows == 0
        else:
            _assert_identical(whole[p], got)


@given(build_rows=st.integers(min_value=1, max_value=200)
       if HAS_HYPOTHESIS else None,
       probe_rows=_rows if HAS_HYPOTHESIS else None,
       seed=_seed if HAS_HYPOTHESIS else None)
@settings(max_examples=40, deadline=None)
def test_prop_spilled_build_join_matches(build_rows, probe_rows, seed):
    """Join over a spilled (mmap) build is a row-for-row exact match of
    ``op_hash_join`` over the in-memory build — probe order, duplicate
    expansion order, dtypes, bits."""
    r = np.random.default_rng(seed)
    build = ColumnBatch({
        "bk": r.integers(0, max(1, build_rows // 2), size=build_rows,
                         dtype=np.int64),
        "bv": r.standard_normal(build_rows).astype(np.float32),
    })
    probe = ColumnBatch({
        "pk": r.integers(0, max(1, build_rows), size=probe_rows,
                         dtype=np.int64),
        "pv": r.standard_normal(probe_rows),
    })
    mem = operators.op_hash_join(probe, build, "pk", "bk")
    spl = operators.op_hash_join(probe, spill.spill_build(build),
                                 "pk", "bk")
    _assert_identical(mem, spl)


@given(cap=_cap if HAS_HYPOTHESIS else None,
       steps=st.lists(st.tuples(st.integers(min_value=0, max_value=2),
                                st.integers(min_value=0, max_value=2048)),
                      max_size=60) if HAS_HYPOTHESIS else None)
@settings(max_examples=60, deadline=None)
def test_prop_budget_invariants(cap, steps):
    """Under arbitrary try_reserve/forced-reserve/release sequences:
    ``reserved == sum(grant.used)``, ``try_reserve`` never passes the
    cap, and ``peak <= cap`` unless a forced reservation happened (in
    which case the overshoot is in ``overcommit_bytes``)."""
    budget = core_memory.MemoryBudget(cap)
    grants = [budget.grant(f"g{i}") for i in range(3)]
    forced = False
    for i, (kind, n) in enumerate(steps):
        g = grants[i % 3]
        if kind == 0:
            before = budget.reserved_bytes
            ok = g.try_reserve(n)
            if ok:
                assert budget.reserved_bytes == before + n <= cap
            else:
                assert budget.reserved_bytes == before  # refusal is free
                assert before + n > cap
        elif kind == 1:
            g.reserve(n, force=True)
            forced = forced or budget.reserved_bytes > cap
        else:
            g.release(min(n, g.used))
        assert budget.reserved_bytes == sum(x.used for x in grants)
        assert budget.reserved_bytes >= 0
        if not forced:
            assert budget.peak_bytes <= cap
        elif budget.peak_bytes > cap:
            assert budget.overcommit_bytes >= budget.peak_bytes - cap
    for g in grants:
        g.release_all()
    assert budget.reserved_bytes == 0


# ---------------------------------------------------------------------------
# Optimizer: memory-derived fan-out
# ---------------------------------------------------------------------------

def test_memory_fanout_term():
    mib = 1024.0 ** 2
    assert optimizer.memory_fanout(None, 64 * mib) == 1
    assert optimizer.memory_fanout(100 * mib, None) == 1
    # 100 MiB input vs a 64 MiB cap (32 MiB window) -> >= 4 fragments.
    assert optimizer.memory_fanout(100 * mib, 64 * mib) == 4
    # derive_fanout takes the max of the throughput and memory terms,
    # still clamped to MAX_SHUFFLE_PARTITIONS.
    n_plain = optimizer.derive_fanout(100 * mib, "jit")
    n_mem = optimizer.derive_fanout(100 * mib, "jit",
                                    memory_budget=64 * mib)
    assert n_mem >= max(n_plain, 4)
    assert optimizer.derive_fanout(1e12, "jit", memory_budget=64 * mib) \
        == optimizer.MAX_SHUFFLE_PARTITIONS


def test_lowering_traces_memory_pressure():
    mib = 1024.0 ** 2
    stats = optimizer.Stats({"lineitem": 4096 * mib})
    _, report = optimizer.lower(queries.q1_logical(), stats=stats,
                                backend="jit", memory_budget=64 * mib)
    assert any("memory pressure" in r for r in report.rules)
    _, report2 = optimizer.lower(queries.q1_logical(), stats=stats,
                                 backend="jit")
    assert not any("memory pressure" in r for r in report2.rules)
