"""Logical query API + optimizing planner: expression builder grammar,
per-rule golden-plan tests (pruning, pushdown, agg split, build side,
fan-out), plan validation, builder-vs-hand-built result parity on all
four paper queries on both backends, and logical->physical->JSON
round-trip stability."""
import json

import numpy as np
import pytest

import golden_plans
from hypo_compat import HAS_HYPOTHESIS, given, settings, st
from repro.core.storage_service import ObjectStore
from repro.engine import columnar, datagen, explain, optimizer, queries
from repro.engine.columnar import ColumnBatch
from repro.engine.coordinator import Coordinator
from repro.engine.logical import (LogicalError, col, count_, lit, max_,
                                  min_, scan, sum_)
from repro.engine.plans import (CollectOutput, Pipeline, PlanValidationError,
                                QueryPlan, ShuffleInput, ShuffleOutput,
                                TableInput)


# ---------------------------------------------------------------------------
# Expression builder -> grammar
# ---------------------------------------------------------------------------

def test_expr_comparisons_emit_grammar():
    assert (col("a") < 5).node == ["lt", "a", 5]
    assert (col("a") <= 5).node == ["le", "a", 5]
    assert (col("a") > 5).node == ["gt", "a", 5]
    assert (col("a") >= 5).node == ["ge", "a", 5]
    assert (col("a") == 5).node == ["eq", "a", 5]
    assert (col("a") != 5).node == ["ne", "a", 5]
    assert (col("a") < col("b")).node == ["ltcol", "a", "b"]
    assert (col("a") > col("b")).node == ["ltcol", "b", "a"]
    assert (col("a") < lit(5)).node == ["lt", "a", 5]
    assert col("d").between(0.05, 0.07).node == ["between", "d", 0.05, 0.07]
    assert col("m").isin([2, 5]).node == ["in", "m", [2, 5]]


def test_expr_boolean_flattening():
    e = (col("a") < 1) & (col("b") < 2) & (col("c") < 3)
    assert e.node == ["and", ["lt", "a", 1], ["lt", "b", 2], ["lt", "c", 3]]
    o = (col("a") < 1) | (col("b") < 2)
    assert o.node == ["or", ["lt", "a", 1], ["lt", "b", 2]]


def test_expr_arithmetic_emits_grammar():
    assert (col("a") * col("b")).node == ["mul", "a", "b"]
    assert (col("a") + col("b")).node == ["add", "a", "b"]
    assert (col("a") - col("b")).node == ["sub", "a", "b"]
    assert (col("a") / col("b")).node == ["div", "a", "b"]
    assert (1 - col("a")).node == ["sub1", "a"]
    assert (1 + col("a")).node == ["add1", "a"]
    assert (col("a") * 2.5).node == ["mul", "a", ["const", 2.5]]
    assert (3 - col("a")).node == ["sub", ["const", 3], "a"]
    assert col("p").case_in([0, 1]).node == ["case_in", "p", [0, 1]]
    nested = (col("x") * (1 - col("d"))) * (1 + col("t"))
    assert nested.node == ["mul", ["mul", "x", ["sub1", "d"]],
                           ["add1", "t"]]


def test_expr_has_no_truth_value():
    """Python `and`/`or`/`not` would silently drop operands; Expr must
    refuse bool coercion (the pandas/polars convention)."""
    with pytest.raises(LogicalError, match="truth value"):
        (col("a") < 1) and (col("b") < 2)   # noqa: B015
    with pytest.raises(LogicalError, match="truth value"):
        not (col("a") < 1)
    with pytest.raises(LogicalError, match="truth value"):
        1 <= col("a") < 5                   # noqa: B015 — chained cmp


def test_scan_empty_column_list_is_not_inferred():
    """scan('t', []) must keep the explicit empty list (an error at
    lowering), not silently switch to infer-everything."""
    from repro.engine.logical import Scan
    assert scan("t", []).node == Scan("t", [])
    assert scan("t").node == Scan("t", None)


def test_expr_rejects_ungrammatical_shapes():
    with pytest.raises(LogicalError):
        (col("a") * col("b")) < 5          # derived LHS needs projection
    with pytest.raises(LogicalError):
        col("a") >= col("b")               # no gecol in the grammar
    with pytest.raises(LogicalError):
        (col("a") < 5) & col("b")          # value in boolean context
    with pytest.raises(LogicalError):
        scan("t").select((col("a") * col("b")))   # derived without alias


def test_new_grammar_ops_evaluate_on_both_backends():
    from repro.engine import compile as engine_compile
    batch = ColumnBatch({"a": np.asarray([1.0, 4.0, 9.0]),
                         "b": np.asarray([2.0, 2.0, 2.0])})
    ops = [{"op": "filter", "expr": ["gt", "a", 1.5]},
           {"op": "filter", "expr": ["ne", "a", 9.0]},
           {"op": "project", "columns": [
               ["d", ["div", "a", "b"]], ["s", ["sub", "a", "b"]]]}]
    out_np = engine_compile.run_pipeline(batch, ops, backend="numpy")
    out_jit = engine_compile.run_pipeline(batch, ops, backend="jit")
    for out in (out_np, out_jit):
        assert out["d"].tolist() == [2.0]
        assert out["s"].tolist() == [2.0]


def test_agg_helpers():
    a = sum_("x")
    assert (a.fn, a.column, a.name) == ("sum", "x", "sum_x")
    assert count_(col("x")).alias("n").name == "n"
    assert min_("x").fn == "min" and max_("x").fn == "max"


# ---------------------------------------------------------------------------
# Optimizer rules (golden-plan unit tests)
# ---------------------------------------------------------------------------

def test_projection_pruning_narrows_scan_to_referenced_columns():
    plan = queries.q6_plan()
    scan_pipe = plan.pipelines[0]
    assert isinstance(scan_pipe.input, TableInput)
    assert scan_pipe.input.columns == sorted(
        ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"])


def test_projection_pruning_drops_unused_selected_column():
    q = (scan("t", ["a", "b", "c"])
         .select("a", "b", "c")
         .group_by("a").agg(sum_("b").alias("s"))
         .collect("prune"))
    plan = optimizer.plan(q)
    assert plan.pipelines[0].input.columns == ["a", "b"]
    proj = plan.pipelines[0].ops[0]
    assert proj == {"op": "project", "columns": ["a", "b"]}


def test_predicate_pushdown_through_projection_rename():
    q = (scan("t")
         .select("k", (col("x") * col("y")).alias("v"),
                 col("a").alias("a2"))
         .filter((col("a2") < 5) & (col("v") > 1.0))
         .group_by("k").agg(sum_("v").alias("sv"))
         .collect("push"))
    plan, report = optimizer.lower(q)
    ops = plan.pipelines[0].ops
    # The a2 conjunct crossed the projection (renamed back to a); the
    # derived-column conjunct stayed above it.
    assert ops[0] == {"op": "filter", "expr": ["lt", "a", 5]}
    assert ops[1]["op"] == "project"
    assert ops[2] == {"op": "filter", "expr": ["gt", "v", 1.0]}
    assert any("predicate_pushdown" in r for r in report.rules)


def test_predicate_pushdown_splits_by_join_side():
    left = scan("lt").select("k", "lv")
    right = scan("rt").select("rk", "rv")
    q = (left.join(right, on=("k", "rk"))
         .filter((col("lv") < 1.0) & (col("rv") > 2.0))
         .select("k", "lv", "rv")
         .collect("jpush", shuffle_partitions=4))
    plan, report = optimizer.lower(q)
    by_name = {p.name: p for p in plan.pipelines}
    assert by_name["scan_lt"].ops[0]["expr"] == ["lt", "lv", 1.0]
    assert by_name["scan_rt"].ops[0]["expr"] == ["gt", "rv", 2.0]
    # Nothing left to filter after the join itself.
    join_pipe = by_name["join"]
    assert [op["op"] for op in join_pipe.ops] == ["hash_join", "project"]
    assert sum("predicate_pushdown" in r for r in report.rules) == 2


def test_agg_split_partial_then_final_count_as_sum():
    plan = queries.q1_plan()
    assert [p.name for p in plan.pipelines] == ["scan_lineitem", "final_agg"]
    partial = plan.pipelines[0].ops[-1]
    final = plan.pipelines[1].ops[0]
    assert partial["op"] == final["op"] == "hash_agg"
    assert ["count_order", "count", "l_quantity"] in partial["aggs"]
    # Count partials re-aggregate as sums over the partial output column.
    assert ["count_order", "sum", "count_order"] in final["aggs"]
    assert all(fn == "sum" for _, fn, _ in final["aggs"])
    # The combine shuffle partitions by the first group key, fan-out 1.
    out = plan.pipelines[0].output
    assert (out.partition_by, out.partitions) == ("l_returnflag", 1)


def test_global_agg_split_needs_no_fake_partition_column():
    plan = queries.q6_plan()
    text = plan.to_json()
    assert "__zero__" not in text
    out = plan.pipelines[0].output
    assert isinstance(out, ShuffleOutput)
    assert (out.partition_by, out.partitions) == ("revenue", 1)


def test_zero_hack_retired_from_queries_source():
    import inspect
    assert "__zero__" not in inspect.getsource(queries)


def test_min_max_final_aggs_preserved():
    q = (scan("t", ["k", "v"]).group_by("k")
         .agg(min_("v").alias("lo"), max_("v").alias("hi"),
              count_("v").alias("n"))
         .collect("mm"))
    plan = optimizer.plan(q)
    final = {name: fn for name, fn, _ in plan.pipelines[1].ops[0]["aggs"]}
    assert final == {"lo": "min", "hi": "max", "n": "sum"}


def _fake_profile(tmp_path, mib_per_s: float):
    path = tmp_path / "BENCH_fake.json"
    path.write_text(json.dumps(
        {"pipeline": {"batch_mib": mib_per_s, "numpy_s": 1.0}}))
    return str(path)


def test_partition_count_from_stats_and_measured_throughput(tmp_path):
    bench = _fake_profile(tmp_path, mib_per_s=100.0)   # 100 MiB/s
    mib = 1024.0 ** 2
    stats = optimizer.Stats({"big": 300.0 * mib, "small": 50.0 * mib})
    q = (scan("big", ["k", "v"]).select("k", "v")
         .join(scan("small", ["rk", "rv"]).select("rk", "rv"),
               on=("k", "rk"))
         .select("k", "v", "rv")
         .collect("fanout"))     # no shuffle_partitions hint
    plan, report = optimizer.lower(q, stats=stats, bench_path=bench)
    shuffles = {p.name: p.output for p in plan.pipelines
                if isinstance(p.output, ShuffleOutput)}
    # 300 MiB at 100 MiB/s and 0.25 s/partition -> ceil(300/25) = 12.
    assert shuffles["scan_big"].partitions == 12
    assert shuffles["scan_small"].partitions == 12   # co-partitioned
    assert any("shuffle_fanout" in r and "12 partitions" in r
               for r in report.rules)


def test_partition_count_hint_wins():
    q = queries.q12_logical(shuffle_partitions=16)
    plan = optimizer.plan(q)
    assert plan.pipelines[0].output.partitions == 16


def test_aggregate_combine_ignores_row_shuffle_hint():
    """The shuffle_partitions hint pins ROW shuffles only: after the
    agg-split pass the combine data is tiny, so a hinted wide combine
    would just schedule mostly-empty final fragments. bb_q3's row
    shuffle is its user exchange, which takes the hint; the session
    pipeline pre-aggregates and combines at fan-out 1."""
    plan = queries.bb_q3_plan("tables/item/part-00000",
                              shuffle_partitions=8)
    assert plan.pipelines[0].output.partition_by == "wcs_user_sk"
    assert plan.pipelines[0].output.partitions == 8
    assert plan.pipelines[1].output.partition_by == "viewed_item"
    assert plan.pipelines[1].output.partitions == 1
    # Same for keyed combines in hinted join queries (q12: 8-way join
    # shuffles, 1-way combine) and for global aggregates.
    q12 = queries.q12_plan(shuffle_partitions=8)
    combine = next(p for p in q12.pipelines if p.name == "join_agg")
    assert combine.output.partitions == 1
    q6 = queries.q6_logical()
    q6.shuffle_partitions = 8
    assert optimizer.plan(q6).pipelines[0].output.partitions == 1


def test_partition_count_clamped(tmp_path):
    bench = _fake_profile(tmp_path, mib_per_s=1.0)     # 1 MiB/s: tiny target
    mib = 1024.0 ** 2
    stats = optimizer.Stats({"big": 10000.0 * mib, "small": 1.0 * mib})
    q = (scan("big", ["k"]).select("k")
         .join(scan("small", ["rk"]).select("rk"), on=("k", "rk"))
         .select("k").collect("clamp"))
    plan = optimizer.plan(q, stats=stats, bench_path=bench)
    assert plan.pipelines[0].output.partitions == \
        optimizer.MAX_SHUFFLE_PARTITIONS


def test_global_agg_combine_forced_to_one_partition(tmp_path):
    """A keyless aggregate partitions its combine shuffle by a partial
    VALUE, so fan-out must be pinned at 1 even when the cost model (here:
    an absurdly slow measured throughput) would fan a keyed combine out."""
    bench = _fake_profile(tmp_path, mib_per_s=0.001)
    mib = 1024.0 ** 2
    stats = optimizer.Stats({"t": 100.0 * mib})
    keyed = (scan("t", ["k", "v"]).group_by("k").agg(sum_("v").alias("s"))
             .collect("keyed"))
    keyed_plan = optimizer.plan(keyed, stats=stats, bench_path=bench)
    assert keyed_plan.pipelines[0].output.partitions > 1   # model fans out
    glob = scan("t", ["k", "v"]).agg(sum_("v").alias("s")).collect("glob")
    glob_plan = optimizer.plan(glob, stats=stats, bench_path=bench)
    assert glob_plan.pipelines[0].output.partitions == 1   # forced


def test_keyed_combine_fans_out_for_large_inputs(tmp_path):
    """The combine estimate scales with the pre-agg input, so a huge
    grouped input fans its combine shuffle out instead of serializing
    the final aggregation in one fragment."""
    bench = _fake_profile(tmp_path, mib_per_s=100.0)   # target 25 MiB
    mib = 1024.0 ** 2
    stats = optimizer.Stats({"big": 10000.0 * mib})
    q = (scan("big", ["k", "v"]).group_by("k").agg(sum_("v").alias("s"))
         .collect("bigagg"))
    plan = optimizer.plan(q, stats=stats, bench_path=bench)
    # 10000 MiB * 0.05 = 500 MiB -> ceil(500/25) = 20 combine partitions.
    assert plan.pipelines[0].output.partitions == 20


def test_build_side_prefers_smaller_estimated_input():
    mib = 1024.0 ** 2
    stats = optimizer.Stats({"fact": 500.0 * mib, "dim": 10.0 * mib})
    fact = scan("fact", ["k", "v"]).select("k", "v")
    dim = scan("dim", ["dk", "dv"]).select("dk", "dv")
    # Authored with the big table on the RIGHT: the optimizer must swap
    # so the small side builds the hash table.
    q = (dim.join(fact, on=("dk", "k")).select("dk", "v")
         .collect("swap"))
    plan, report = optimizer.lower(q, stats=stats)
    join_pipe = next(p for p in plan.pipelines if p.input2 is not None)
    assert join_pipe.input.from_pipeline == "scan_fact"     # probe
    assert join_pipe.input2.from_pipeline == "scan_dim"     # build
    join_op = join_pipe.ops[0]
    assert join_op["left_key"] == "k" and join_op["right_key"] == "dk"
    assert any("join_build_side: build = left" in r for r in report.rules)


def test_build_side_swap_preserves_logical_join_schema(loaded_store):
    """Swapping the build side must not change which join-key column the
    downstream ops see: the physical join drops the build key, so a
    swapped join re-exposes the logical left key via a rename projection
    (regression test for a worker-side KeyError)."""
    mib = 1024.0 ** 2
    stats = optimizer.Stats({"lineitem": 1.0 * mib, "orders": 1000.0 * mib})
    li = scan("lineitem", ["l_orderkey", "l_quantity"]) \
        .select("l_orderkey", "l_quantity")
    orders = scan("orders", ["o_orderkey", "o_totalprice"]) \
        .select("o_orderkey", "o_totalprice")
    # Downstream references the LEFT join key after the join.
    q = (li.join(orders, on=("l_orderkey", "o_orderkey"))
         .select("l_orderkey", "l_quantity", "o_totalprice")
         .group_by("l_orderkey")
         .agg(sum_("o_totalprice").alias("tp"))
         .collect("swap_schema"))
    plan, report = optimizer.lower(q, stats=stats)
    assert any("join_build_side: build = left" in r for r in report.rules)
    join_pipe = next(p for p in plan.pipelines if p.input2 is not None)
    assert join_pipe.input2.from_pipeline == "scan_lineitem"
    # The rename projection restores the logical schema.
    assert join_pipe.ops[1]["columns"][0] == ["l_orderkey", "o_orderkey"]
    # And the plan actually runs end to end.
    store, keys = loaded_store
    c = _coordinator(store, keys, "numpy")
    res = c.execute(plan, "lp-swap-schema")
    li_full = _full(store, keys["lineitem"])
    o_full = _full(store, keys["orders"])
    prices = dict(zip(o_full["o_orderkey"].tolist(),
                      o_full["o_totalprice"].tolist()))
    want: dict = {}
    for k in li_full["l_orderkey"].tolist():
        if k in prices:
            want[k] = want.get(k, 0.0) + prices[k]
    got = dict(zip(res.result["l_orderkey"].tolist(),
                   res.result["tp"].tolist()))
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9)


def test_validate_op_reads_missing_column():
    plan = QueryPlan("bad", [
        _pipe(ops=[{"op": "project", "columns": ["k", "typo_col"]}])])
    with pytest.raises(PlanValidationError, match="typo_col"):
        plan.validate()
    plan2 = QueryPlan("bad2", [
        _pipe(ops=[{"op": "filter", "expr": ["lt", "missing", 5]}])])
    with pytest.raises(PlanValidationError, match="missing"):
        plan2.validate()
    plan3 = QueryPlan("bad3", [
        _pipe(ops=[{"op": "hash_agg", "keys": ["k"],
                    "aggs": [["s", "sum", "ghost"]]}])])
    with pytest.raises(PlanValidationError, match="ghost"):
        plan3.validate()


def test_build_side_defaults_to_right_without_stats():
    plan = queries.q12_plan()
    join_pipe = next(p for p in plan.pipelines if p.input2 is not None)
    assert join_pipe.input.from_pipeline == "scan_lineitem"
    assert join_pipe.input2.from_pipeline == "scan_orders"


def test_bare_scan_below_udf_requires_columns():
    q = (scan("clicks").map_udf("clicks_before_purchase")
         .group_by("viewed_item").agg(sum_("n").alias("views"))
         .collect("bad"))
    with pytest.raises(LogicalError, match="explicit columns"):
        optimizer.plan(q)


# ---------------------------------------------------------------------------
# QueryPlan.validate()
# ---------------------------------------------------------------------------

def _pipe(name="p", deps=(), ops=(), output=None, input2=None,
          table_cols=("k", "v")):
    inp = ShuffleInput(deps[0]) if deps else TableInput("t", list(table_cols))
    return Pipeline(name=name, input=inp, ops=list(ops),
                    output=output or CollectOutput(), input2=input2)


def test_validate_duplicate_pipeline_names():
    plan = QueryPlan("bad", [
        _pipe("p", output=ShuffleOutput("k", 1)),
        Pipeline("p", ShuffleInput("p"), [], CollectOutput())])
    with pytest.raises(PlanValidationError, match="duplicate"):
        plan.validate()


def test_validate_dangling_and_out_of_order_shuffle_inputs():
    plan = QueryPlan("bad", [_pipe("c", deps=("ghost",))])
    with pytest.raises(PlanValidationError, match="dangling"):
        plan.validate()
    plan2 = QueryPlan("bad2", [
        Pipeline("c", ShuffleInput("p"), [], CollectOutput()),
        _pipe("p", output=ShuffleOutput("k", 1))])
    with pytest.raises(PlanValidationError, match="out-of-order"):
        plan2.validate()


def test_validate_unknown_op():
    plan = QueryPlan("bad", [_pipe(ops=[{"op": "sort_merge"}])])
    with pytest.raises(PlanValidationError, match="unknown op"):
        plan.validate()


def test_validate_join_without_build_input():
    plan = QueryPlan("bad", [_pipe(ops=[
        {"op": "hash_join", "left_key": "k", "right_key": "rk"}])])
    with pytest.raises(PlanValidationError, match="without a build-side"):
        plan.validate()


def test_validate_partition_key_not_produced():
    plan = QueryPlan("bad", [
        _pipe("p", ops=[{"op": "project", "columns": ["k"]}],
              output=ShuffleOutput("v", 4)),
        Pipeline("c", ShuffleInput("p"), [], CollectOutput())])
    with pytest.raises(PlanValidationError, match="not produced upstream"):
        plan.validate()


def test_validate_shuffle_input_from_collect_producer():
    """A consumer reading shuffle objects a collect-output producer never
    writes would see silently-empty input (missing_ok) — validate()
    rejects the wiring up front."""
    plan = QueryPlan("bad", [
        _pipe("p"),                           # collect output
        Pipeline("c", ShuffleInput("p"), [], CollectOutput())])
    with pytest.raises(PlanValidationError,
                       match="does not produce a shuffle output"):
        plan.validate()


def test_validate_terminal_must_collect():
    plan = QueryPlan("bad", [_pipe("p", output=ShuffleOutput("k", 2))])
    with pytest.raises(PlanValidationError, match="must collect"):
        plan.validate()


def test_coordinator_validates_before_scheduling():
    c = Coordinator(ObjectStore(), mode="elastic")
    plan = QueryPlan("bad", [_pipe("c", deps=("ghost",))])
    with pytest.raises(PlanValidationError):
        c.execute(plan)


def test_handbuilt_golden_plans_validate():
    golden_plans.q1_plan_handbuilt().validate()
    golden_plans.q6_plan_handbuilt().validate()
    golden_plans.q12_plan_handbuilt().validate()
    golden_plans.bb_q3_plan_handbuilt("tables/item/part-00000").validate()


# ---------------------------------------------------------------------------
# Builder-vs-hand-built parity on both backends
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


def _full(store, keys):
    return ColumnBatch.concat(
        [columnar.deserialize(store.get(k)) for k in keys])


def _coordinator(store, keys, backend):
    c = Coordinator(store, mode="elastic", backend=backend)
    for t in ("lineitem", "orders", "clickstreams"):
        c.register_table(t, keys[t])
    return c


def _rows(batch: ColumnBatch, key_cols):
    order = np.lexsort([np.asarray(batch[k]) for k in key_cols][::-1])
    return {k: np.asarray(v, np.float64)[order] for k, v in batch.items()}


def _assert_rows_close(a, b, rtol):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=rtol)


@pytest.mark.parametrize("backend", ["numpy", "jit"])
def test_parity_q6(loaded_store, backend):
    store, keys = loaded_store
    c = _coordinator(store, keys, backend)
    rtol = 1e-9 if backend == "numpy" else 1e-6   # jit float contract (docs/BACKENDS.md)
    ref = queries.q6_reference(_full(store, keys["lineitem"]))
    lowered = c.execute(queries.q6_plan(), f"lp-q6-{backend}")
    hand = c.execute(golden_plans.q6_plan_handbuilt(), f"lp-q6h-{backend}")
    assert float(lowered.result["revenue"][0]) == pytest.approx(ref,
                                                               rel=rtol)
    assert float(hand.result["revenue"][0]) == pytest.approx(ref, rel=rtol)


@pytest.mark.parametrize("backend", ["numpy", "jit"])
def test_parity_q1(loaded_store, backend):
    store, keys = loaded_store
    c = _coordinator(store, keys, backend)
    rtol = 1e-9 if backend == "numpy" else 1e-6   # jit float contract (docs/BACKENDS.md)
    ref = queries.q1_reference(_full(store, keys["lineitem"]))
    keycols = ["l_returnflag", "l_linestatus"]
    lowered = c.execute(queries.q1_plan(), f"lp-q1-{backend}")
    hand = c.execute(golden_plans.q1_plan_handbuilt(), f"lp-q1h-{backend}")
    assert lowered.result.num_rows == hand.result.num_rows == ref.num_rows
    _assert_rows_close(_rows(lowered.result, keycols), _rows(ref, keycols),
                       rtol)
    _assert_rows_close(_rows(hand.result, keycols), _rows(ref, keycols),
                       rtol)


@pytest.mark.parametrize("backend", ["numpy", "jit"])
def test_parity_q12(loaded_store, backend):
    store, keys = loaded_store
    c = _coordinator(store, keys, backend)
    rtol = 1e-9 if backend == "numpy" else 1e-6   # jit float contract (docs/BACKENDS.md)
    ref = queries.q12_reference(_full(store, keys["lineitem"]),
                                _full(store, keys["orders"]))
    lowered = c.execute(queries.q12_plan(), f"lp-q12-{backend}")
    hand = c.execute(golden_plans.q12_plan_handbuilt(),
                     f"lp-q12h-{backend}")
    for res in (lowered, hand):
        _assert_rows_close(_rows(res.result, ["l_shipmode"]),
                           _rows(ref, ["l_shipmode"]), rtol)


@pytest.mark.parametrize("backend", ["numpy", "jit"])
def test_parity_bb_q3(loaded_store, backend):
    store, keys = loaded_store
    c = _coordinator(store, keys, backend)
    item = columnar.deserialize(store.get(keys["item"][0]))
    ref = queries.bb_q3_reference(_full(store, keys["clickstreams"]), item)
    out = {}
    for tag, plan in (("lowered", queries.bb_q3_plan(keys["item"][0])),
                      ("hand", golden_plans.bb_q3_plan_handbuilt(
                          keys["item"][0]))):
        res = c.execute(plan, f"lp-bb-{tag}-{backend}")
        out[tag] = dict(zip(res.result["viewed_item"].tolist(),
                            res.result["views"].tolist()))
        assert out[tag] == ref
    assert out["lowered"] == out["hand"]


def test_coordinator_run_accepts_logical_plan(loaded_store):
    store, keys = loaded_store
    c = _coordinator(store, keys, "numpy")
    ref = queries.q6_reference(_full(store, keys["lineitem"]))
    res = c.run(queries.q6_logical(), query_id="lp-run-logical")
    assert float(res.result["revenue"][0]) == pytest.approx(ref, rel=1e-9)
    # Physical plans pass through run() unchanged.
    res2 = c.run(queries.q6_plan(), query_id="lp-run-physical")
    assert float(res2.result["revenue"][0]) == pytest.approx(ref, rel=1e-9)


def test_q12_plan_shape_matches_handbuilt_wiring():
    """The lowered Q12 keeps the hand-built plan's topology: two scans
    co-partitioned on the join keys, a join+partial-agg pipeline, and a
    1-fragment final aggregation."""
    lowered = queries.q12_plan()
    hand = golden_plans.q12_plan_handbuilt()
    assert [p.name for p in lowered.pipelines] == \
        [p.name for p in hand.pipelines]
    for lp, hp in zip(lowered.pipelines, hand.pipelines):
        if isinstance(lp.input, TableInput):
            assert sorted(lp.input.columns) == sorted(hp.input.columns)
            assert lp.output.partition_by == hp.output.partition_by
            assert lp.output.partitions == hp.output.partitions
    lj = next(p for p in lowered.pipelines if p.name == "join_agg")
    assert lj.ops[0] == {"op": "hash_join", "left_key": "l_orderkey",
                         "right_key": "o_orderkey"}


# ---------------------------------------------------------------------------
# explain
# ---------------------------------------------------------------------------

def test_explain_renders_all_sections():
    text = explain.explain(queries.q12_logical())
    for expected in ("logical plan", "applied rules", "physical plan",
                     "Join[l_orderkey = o_orderkey]", "projection_pruning",
                     "agg_split", "scan_lineitem", "final_agg"):
        assert expected in text


def test_explain_main_entrypoint(capsys):
    assert explain.main(["tpch_q12"]) == 0
    out = capsys.readouterr().out
    assert "physical plan" in out and "join_agg" in out
    # The explain output names the canonical plan shape that keys the
    # compiled-plan cache.
    assert "plan shape:" in out
    # Unknown query: nonzero exit, and stderr lists the available names
    # so the user can correct the invocation without reading the source.
    assert explain.main(["nope"]) == 2
    err = capsys.readouterr().err
    assert "unknown query 'nope'" in err
    for name in ("tpch_q1", "tpch_q6", "tpch_q12", "tpcxbb_q3"):
        assert name in err


# ---------------------------------------------------------------------------
# Round-trip stability: logical -> physical -> JSON -> physical
# ---------------------------------------------------------------------------

_COLS = ["l_shipdate", "l_quantity", "l_discount", "l_extendedprice"]

if HAS_HYPOTHESIS:
    _pred_st = st.sampled_from(_COLS).flatmap(lambda c: st.one_of(
        st.floats(0.0, 100.0).map(lambda v: col(c) < v),
        st.floats(0.0, 100.0).map(lambda v: col(c) >= v),
        st.lists(st.integers(0, 9), min_size=1, max_size=3)
        .map(lambda vs: col(c).isin(vs)),
    ))
else:   # strategies never drawn; @given skips the test
    _pred_st = None


@given(preds=st.lists(_pred_st, min_size=1, max_size=3),
       keyed=st.booleans(), partitions=st.integers(1, 32))
@settings(max_examples=30, deadline=None)
def test_lowered_plan_json_roundtrip_stable(preds, keyed, partitions):
    q = scan("lineitem")
    for p in preds:
        q = q.filter(p)
    q = q.select("l_quantity", "l_discount",
                 (col("l_extendedprice") * (1 - col("l_discount")))
                 .alias("disc_price"))
    grouped = q.group_by("l_quantity") if keyed else q
    q = grouped.agg(sum_("disc_price").alias("s"),
                    count_("l_discount").alias("n"))
    query = q.collect("roundtrip", shuffle_partitions=partitions)
    plan = optimizer.plan(query)
    text = plan.to_json()
    back = QueryPlan.from_json(text)
    back.validate()
    assert json.loads(back.to_json()) == json.loads(text)
    # Lowering is deterministic: same logical plan, same physical JSON.
    assert optimizer.plan(query).to_json() == text


def test_paper_query_plans_json_roundtrip_stable():
    plans = [queries.q1_plan(), queries.q6_plan(), queries.q12_plan(),
             queries.bb_q3_plan("tables/item/part-00000")]
    for plan in plans:
        text = plan.to_json()
        back = QueryPlan.from_json(text)
        back.validate()
        assert json.loads(back.to_json()) == json.loads(text)
