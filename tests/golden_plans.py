"""Golden parity fixtures: the pre-logical hand-built physical plans.

These are the original hand-wired ``QueryPlan`` builders from
``queries.py`` before the logical-API rewrite (including the retired
``__zero__`` fake-partition-key idiom). They exist so the planner tests
can prove that builder-authored, optimizer-lowered plans return the same
results as the plans a human wired by hand — do not "modernize" them.
"""
from __future__ import annotations

from repro.engine import datagen
from repro.engine.plans import (CollectOutput, Pipeline, QueryPlan,
                                ShuffleInput, ShuffleOutput, TableInput)
from repro.engine.queries import HIGH, MAIL, SHIP, URGENT


def q6_plan_handbuilt(shipdate_lo: int = datagen.DATE_1994_01_01,
                      discount: float = 0.06,
                      quantity: float = 24.0) -> QueryPlan:
    pred = ["and",
            ["ge", "l_shipdate", shipdate_lo],
            ["lt", "l_shipdate", shipdate_lo + 365],
            ["between", "l_discount", round(discount - 0.01, 2),
             round(discount + 0.01, 2)],
            ["lt", "l_quantity", quantity]]
    scan = Pipeline(
        name="scan_lineitem",
        input=TableInput("lineitem", ["l_shipdate", "l_discount",
                                      "l_quantity", "l_extendedprice"]),
        ops=[{"op": "filter", "expr": pred},
             {"op": "project",
              "columns": [["revenue", ["mul", "l_extendedprice",
                                       "l_discount"]]]},
             {"op": "hash_agg", "keys": [],
              "aggs": [["revenue", "sum", "revenue"]]},
             {"op": "project",
              "columns": ["revenue", ["__zero__", ["const", 0]]]}],
        output=ShuffleOutput(partition_by="__zero__", partitions=1))
    final = Pipeline(
        name="final_agg",
        input=ShuffleInput("scan_lineitem"),
        ops=[{"op": "hash_agg", "keys": [],
              "aggs": [["revenue", "sum", "revenue"]]}],
        output=CollectOutput())
    return QueryPlan("tpch_q6", [scan, final])


_Q1_AGGS = [["sum_qty", "sum", "l_quantity"],
            ["sum_base_price", "sum", "l_extendedprice"],
            ["sum_disc_price", "sum", "disc_price"],
            ["sum_charge", "sum", "charge"],
            ["sum_disc", "sum", "l_discount"],
            ["count_order", "count", "l_quantity"]]


def q1_plan_handbuilt(delta_days: int = 90) -> QueryPlan:
    cutoff = datagen.DATE_MAX - delta_days
    scan = Pipeline(
        name="scan_lineitem",
        input=TableInput("lineitem", ["l_shipdate", "l_quantity",
                                      "l_extendedprice", "l_discount",
                                      "l_tax", "l_returnflag",
                                      "l_linestatus"]),
        ops=[{"op": "filter", "expr": ["le", "l_shipdate", cutoff]},
             {"op": "project", "columns": [
                 "l_returnflag", "l_linestatus", "l_quantity",
                 "l_extendedprice", "l_discount",
                 ["disc_price", ["mul", "l_extendedprice",
                                 ["sub1", "l_discount"]]],
                 ["charge", ["mul", ["mul", "l_extendedprice",
                                     ["sub1", "l_discount"]],
                             ["add1", "l_tax"]]]]},
             {"op": "hash_agg", "keys": ["l_returnflag", "l_linestatus"],
              "aggs": _Q1_AGGS}],
        output=ShuffleOutput(partition_by="l_returnflag", partitions=1))
    # Count partials re-aggregate as sums after the shuffle.
    final_aggs = [[name, "sum" if fn == "count" else fn, name]
                  for name, fn, _ in _Q1_AGGS]
    final = Pipeline(
        name="final_agg",
        input=ShuffleInput("scan_lineitem"),
        ops=[{"op": "hash_agg", "keys": ["l_returnflag", "l_linestatus"],
              "aggs": final_aggs}],
        output=CollectOutput())
    return QueryPlan("tpch_q1", [scan, final])


def q12_plan_handbuilt(shuffle_partitions: int = 8,
                       year_lo: int = datagen.DATE_1994_01_01) -> QueryPlan:
    li_scan = Pipeline(
        name="scan_lineitem",
        input=TableInput("lineitem", ["l_orderkey", "l_shipmode",
                                      "l_shipdate", "l_commitdate",
                                      "l_receiptdate"]),
        ops=[{"op": "filter", "expr": ["and",
              ["in", "l_shipmode", [MAIL, SHIP]],
              ["ltcol", "l_commitdate", "l_receiptdate"],
              ["ltcol", "l_shipdate", "l_commitdate"],
              ["ge", "l_receiptdate", year_lo],
              ["lt", "l_receiptdate", year_lo + 365]]},
             {"op": "project", "columns": ["l_orderkey", "l_shipmode"]}],
        output=ShuffleOutput(partition_by="l_orderkey",
                             partitions=shuffle_partitions))
    o_scan = Pipeline(
        name="scan_orders",
        input=TableInput("orders", ["o_orderkey", "o_orderpriority"]),
        ops=[{"op": "project", "columns": ["o_orderkey", "o_orderpriority"]}],
        output=ShuffleOutput(partition_by="o_orderkey",
                             partitions=shuffle_partitions))
    join = Pipeline(
        name="join_agg",
        input=ShuffleInput("scan_lineitem"),
        input2=ShuffleInput("scan_orders"),
        ops=[{"op": "hash_join", "left_key": "l_orderkey",
              "right_key": "o_orderkey"},
             {"op": "project", "columns": [
                 "l_shipmode",
                 ["high_line", ["case_in", "o_orderpriority",
                                [URGENT, HIGH]]],
                 ["low_line", ["sub1", ["case_in", "o_orderpriority",
                                        [URGENT, HIGH]]]]]},
             {"op": "hash_agg", "keys": ["l_shipmode"],
              "aggs": [["high_line_count", "sum", "high_line"],
                       ["low_line_count", "sum", "low_line"]]},
             {"op": "project", "columns": [
                 "l_shipmode", "high_line_count", "low_line_count",
                 ["__zero__", ["const", 0]]]}],
        output=ShuffleOutput(partition_by="__zero__", partitions=1))
    final = Pipeline(
        name="final_agg",
        input=ShuffleInput("join_agg"),
        ops=[{"op": "hash_agg", "keys": ["l_shipmode"],
              "aggs": [["high_line_count", "sum", "high_line_count"],
                       ["low_line_count", "sum", "low_line_count"]]}],
        output=CollectOutput())
    return QueryPlan("tpch_q12", [li_scan, o_scan, join, final])


def bb_q3_plan_handbuilt(item_table_key: str, target_category: int = 3,
                         views: int = 5, seconds: int = 864000,
                         shuffle_partitions: int = 8) -> QueryPlan:
    scan_pipe = Pipeline(
        name="scan_clicks",
        input=TableInput("clickstreams", ["wcs_user_sk", "wcs_click_date_sk",
                                          "wcs_click_time_sk", "wcs_item_sk",
                                          "wcs_click_type"]),
        ops=[{"op": "project", "columns": [
            "wcs_user_sk",
            ["tstamp", ["add", ["mul", "wcs_click_date_sk",
                                ["const", 86400]], "wcs_click_time_sk"]],
            "wcs_item_sk", "wcs_click_type"]}],
        output=ShuffleOutput(partition_by="wcs_user_sk",
                             partitions=shuffle_partitions))
    map_pipe = Pipeline(
        name="map_clicks",
        input=ShuffleInput("scan_clicks"),
        ops=[{"op": "udf", "name": "clicks_before_purchase",
              "kwargs": {"target_category": target_category,
                         "views": views, "seconds": seconds},
              "broadcast": {"item_categories": {"key": item_table_key,
                                                "column": "i_category_id"}},
              "partition_by": "wcs_user_sk",
              "order_by": ["wcs_user_sk", "tstamp", "wcs_item_sk",
                           "wcs_click_type"]}],
        output=ShuffleOutput(partition_by="viewed_item",
                             partitions=shuffle_partitions))
    reduce_pipe = Pipeline(
        name="reduce_counts",
        input=ShuffleInput("map_clicks"),
        ops=[{"op": "hash_agg", "keys": ["viewed_item"],
              "aggs": [["views", "sum", "n"]]}],
        output=CollectOutput())
    return QueryPlan("tpcxbb_q3", [scan_pipe, map_pipe, reduce_pipe])
