"""The traffic and the data: every mix resolves to the module of its kind,
its rounds and the tables are a function of the seed, parameters stay in
their specified ranges, and every TPC-H column is made at its width."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import streams  # noqa: E402
from loader import load_module  # noqa: E402

MIXES = {p.stem: json.loads(p.read_text())
         for p in (CHIP / "traffic").glob("*.json")}
SEEDS = [0, 1, 2 ** 31 + 7, 2 ** 40 + 3]
REF = load_module(CHIP / "configs" / "tpch_sf1_reference.py")


def kind(mix):
    return load_module(CHIP / "traffic" / f"{MIXES[mix]['kind']}.py")


def take(mix, seed, n=3, stream="window"):
    it = kind(mix).rounds(MIXES[mix], {}, seed, stream)
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_mix_names_a_kind_with_a_generator_and_a_loop(mix):
    module = kind(mix)
    assert callable(module.rounds) and callable(module.window)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_rounds_are_a_function_of_the_seed(mix):
    for seed in SEEDS:
        assert json.dumps(take(mix, seed)) == json.dumps(take(mix, seed))
    assert json.dumps(take(mix, 1)) != json.dumps(take(mix, 2))
    assert json.dumps(take(mix, 1, stream="window")) \
        != json.dumps(take(mix, 1, stream="warmup"))


def test_query_parameters_stay_in_their_specified_ranges():
    seen = {"delta_days": set(), "year": set(), "discount": set(),
            "quantity": set()}
    for r in take("q1_q6", 9, n=400):
        for item in r:
            for k, v in item["params"].items():
                seen[k].add(v)
    assert seen["delta_days"] == set(range(60, 121))
    assert seen["year"] == set(range(1993, 1998))
    assert seen["discount"] == {round(k * 0.01, 10) for k in range(2, 10)}
    assert seen["quantity"] == {24, 25}
    for r in take("q12", 9, n=20):
        assert r[0]["params"]["shuffle_partitions"] == 8


def test_streams_are_independent_and_take_large_seeds():
    big = 2 ** 40 + 3
    a = streams.rng(big, "data", 0).integers(0, 1 << 30, 4)
    assert (a == streams.rng(big, "data", 0).integers(0, 1 << 30, 4)).all()
    assert (a != streams.rng(big, "data", 1).integers(0, 1 << 30, 4)).any()
    assert (a != streams.rng(big, "window").integers(0, 1 << 30, 4)).any()


# Clause 1.4.1: every column of the two tables, and its stored width.
LINEITEM = {"l_orderkey": "<i8", "l_partkey": "<i8", "l_suppkey": "<i8",
            "l_linenumber": "<i4", "l_quantity": "<f8",
            "l_extendedprice": "<f8", "l_discount": "<f8", "l_tax": "<f8",
            "l_returnflag": "|i1", "l_linestatus": "|i1",
            "l_shipdate": "<i4", "l_commitdate": "<i4",
            "l_receiptdate": "<i4", "l_shipinstruct": "|i1",
            "l_shipmode": "|i1", "l_comment": "|S44"}
ORDERS = {"o_orderkey": "<i8", "o_custkey": "<i8", "o_orderstatus": "|i1",
          "o_totalprice": "<f8", "o_orderdate": "<i4",
          "o_orderpriority": "|i1", "o_clerk": "|S15",
          "o_shippriority": "<i4", "o_comment": "|S79"}


@pytest.mark.parametrize("table,columns", [("lineitem", LINEITEM),
                                           ("orders", ORDERS)])
def test_tables_hold_every_column_at_its_width(table, columns):
    t = REF.GENERATORS[table](5000, streams.rng(3, "data", 0))
    assert {k: v.dtype.str for k, v in t.items()} == columns
    assert list(t) == list(columns)
    assert all(len(v) == 5000 for v in t.values())


@pytest.mark.parametrize("table", ["lineitem", "orders"])
def test_tables_are_a_function_of_the_seed(table):
    def make(seed):
        return REF.GENERATORS[table](3000, streams.rng(seed, "data", 1))
    a, b, c = make(2 ** 40 + 3), make(2 ** 40 + 3), make(5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not all(np.array_equal(a[k], c[k]) for k in a)


def test_comments_are_grammar_text_of_the_specified_lengths():
    li = REF.gen_lineitem(20000, streams.rng(4, "data", 0))
    orders = REF.gen_orders(20000, streams.rng(4, "data", 1))
    for col, lo, hi in ((li["l_comment"], 10, 43),
                        (orders["o_comment"], 19, 78)):
        lengths = np.char.str_len(col)
        assert lengths.min() == lo and lengths.max() == hi
        text = b" ".join(col[:200].tolist()).decode()
        assert set(text) <= set("abcdefghijklmnopqrstuvwxyzT-.;:?! ")


def test_keys_and_clerks_follow_the_specification():
    li = REF.gen_lineitem(50000, streams.rng(6, "data", 0), sf=1)
    assert 1 <= li["l_partkey"].min() and li["l_partkey"].max() <= 200_000
    assert 1 <= li["l_suppkey"].min() and li["l_suppkey"].max() <= 10_000
    assert set(np.unique(li["l_linenumber"])) == set(range(1, 8))
    assert set(np.unique(li["l_shipinstruct"])) == {0, 1, 2, 3}
    # Clause 4.2.3: a part's suppliers are partkey + i x (S/4 + (p-1)/S).
    p, s = 7, 10_000
    want = {(p + i * (s // 4 + (p - 1) // s)) % s + 1 for i in range(4)}
    assert want == {2508, 5008, 7508, 8}
    got = li["l_suppkey"][li["l_partkey"] == p]
    assert set(got.tolist()) <= want
    orders = REF.gen_orders(50000, streams.rng(6, "data", 1), sf=1)
    assert (orders["o_custkey"] % 3 != 0).all()
    assert orders["o_custkey"].max() <= 150_000
    assert (orders["o_shippriority"] == 0).all()
    clerks = orders["o_clerk"]
    assert all(c.startswith(b"Clerk#") and len(c) == 15
               for c in clerks[:100])
    nums = np.asarray([int(c[6:]) for c in clerks[:1000]])
    assert nums.min() >= 1 and nums.max() <= 1000
