"""Plain reference for ``tpch_sf1``: the table generators and the queries'
answers in numpy, independent of the program under test.

The generators make every column of lineitem and orders that the
specification defines (clause 1.4.1), at its width, in the shapes of the
program's synthetic TPC-H tables (uniform keys and dates, two-decimal
prices and rates), kept here so that a change to the program cannot move
the data the yardstick is computed on. Dates are days since 1992-01-01.

Each query's answer is computed in ``float64``, or, for the control, in
``bfloat16``: every value rounded to bfloat16 on load, every arithmetic
result rounded again, and every sum taken as a pairwise tree whose
partial sums are rounded too. That is the reference computed one
precision below the engine's float32, which the comparison has to catch.
"""
from __future__ import annotations

import datetime

import numpy as np

DATE_MIN, DATE_MAX = 0, 2555
EPOCH = datetime.date(1992, 1, 1)
MAIL, SHIP = 2, 5                 # l_shipmode dictionary codes
URGENT, HIGH = 0, 1               # o_orderpriority dictionary codes

# Dictionaries of the fixed-text columns that are stored as codes.
SHIPINSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE",
                "TAKE BACK RETURN"]
ORDERSTATUS = ["F", "O", "P"]

# Words of the specification's text grammar (clause 4.2.2.13), from
# which the comment columns are cut.
WORDS = {
    "noun": "foxes ideas theodolites pinto beans instructions dependencies "
            "excuses platelets asymptotes courts dolphins multipliers "
            "sauternes warthogs frets dinos attainments somas Tiresias "
            "patterns forges braids hockey players frays warhorses dugouts "
            "notornis epitaphs pearls tithes waters orbits gifts sheaves "
            "depths sentiments decoys realms pains grouches escapades",
    "verb": "sleep wake are cajole haggle nag use boost affix detect "
            "integrate maintain nod was lose sublate solve thrash promise "
            "engage hinder print x-ray breach eat grow impress mold poach "
            "serve run dazzle snooze doze unwind kindle play hang believe "
            "doubt",
    "adjective": "furious sly careful blithe quick fluffy slow quiet "
                 "ruthless thin close dogged daring brave stealthy "
                 "permanent enticing idle busy regular final ironic even "
                 "bold silent",
    "adverb": "sometimes always never furiously slyly carefully blithely "
              "quickly fluffily slowly quietly ruthlessly thinly closely "
              "doggedly daringly bravely stealthily permanently enticingly "
              "idly busily regularly finally ironically evenly boldly "
              "silently",
    "terminator": ". ; : ? ! --",
}
SENTENCE = ("adjective", "noun", "adverb", "verb", "terminator")
TEXT_POOL_BYTES = 1 << 20


def first_of_year(year: int) -> int:
    return (datetime.date(year, 1, 1) - EPOCH).days


def text_pool(r: np.random.Generator) -> np.ndarray:
    """About a MiB of the grammar's sentences, as bytes: the pool that
    the specification's generator cuts its comments from."""
    words = {k: np.asarray(v.split()) for k, v in WORDS.items()}
    n = TEXT_POOL_BYTES // 30
    parts = [words[k][r.integers(0, len(words[k]), n)] for k in SENTENCE]
    text = " ".join(" ".join(s) for s in zip(*parts)).encode()
    return np.frombuffer(text[:TEXT_POOL_BYTES], np.uint8)


def text_column(r: np.random.Generator, pool: np.ndarray, rows: int,
                lo: int, hi: int, width: int) -> np.ndarray:
    """A variable-text column of ``width`` bytes: each value a random
    cut of ``lo`` to ``hi`` bytes of the pool, padded with zero bytes."""
    lengths = r.integers(lo, hi + 1, rows)
    starts = r.integers(0, len(pool) - width, rows)
    windows = np.lib.stride_tricks.sliding_window_view(pool, width)
    out = np.empty((rows, width), np.uint8)
    step = 1 << 20
    for a in range(0, rows, step):
        b = min(rows, a + step)
        block = windows[starts[a:b]]
        block[np.arange(width)[None, :] >= lengths[a:b, None]] = 0
        out[a:b] = block
    return out.view(f"S{width}").ravel()


def clerk_column(r: np.random.Generator, rows: int, clerks: int):
    """``Clerk#`` and a nine-digit number in 1..clerks, 15 bytes."""
    num = r.integers(1, clerks + 1, rows)
    out = np.empty((rows, 15), np.uint8)
    out[:, :6] = np.frombuffer(b"Clerk#", np.uint8)
    for j in range(9):
        out[:, 6 + j] = ord("0") + num // 10 ** (8 - j) % 10
    return out.view("S15").ravel()


def gen_lineitem(rows: int, r: np.random.Generator, sf: int = 1) -> dict:
    """Every lineitem column of clause 1.4.1 at its width: identifiers
    int64, decimals float64, dates int32 days, fixed text as dictionary
    codes, the comment as 44 bytes."""
    orderkey = r.integers(1, max(2, rows // 4), size=rows, dtype=np.int64)
    ship = r.integers(DATE_MIN, DATE_MAX - 122, size=rows, dtype=np.int32)
    commit = ship + r.integers(-30, 61, size=rows, dtype=np.int32)
    receipt = ship + r.integers(1, 31, size=rows, dtype=np.int32)
    quantity = r.integers(1, 51, size=rows).astype(np.float64)
    price = np.round(r.uniform(900.0, 105000.0, rows), 2)
    discount = np.round(r.integers(0, 11, size=rows) * 0.01, 2)
    tax = np.round(r.integers(0, 9, size=rows) * 0.01, 2)
    returnflag = r.integers(0, 3, size=rows, dtype=np.int8)
    linestatus = r.integers(0, 2, size=rows, dtype=np.int8)
    shipmode = r.integers(0, 7, size=rows, dtype=np.int8)
    # Drawn after the columns above, so those stay as they were.
    suppliers = 10_000 * sf
    partkey = r.integers(1, 200_000 * sf + 1, size=rows, dtype=np.int64)
    i = r.integers(0, 4, size=rows, dtype=np.int64)
    suppkey = (partkey + i * (suppliers // 4 + (partkey - 1) // suppliers)) \
        % suppliers + 1
    return {
        "l_orderkey": orderkey,
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": r.integers(1, 8, size=rows, dtype=np.int32),
        "l_quantity": quantity,
        "l_extendedprice": price,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": ship,
        "l_commitdate": commit,
        "l_receiptdate": receipt,
        "l_shipinstruct": r.integers(0, len(SHIPINSTRUCT), size=rows,
                                     dtype=np.int8),
        "l_shipmode": shipmode,
        "l_comment": text_column(r, text_pool(r), rows, 10, 43, 44),
    }


def gen_orders(rows: int, r: np.random.Generator, sf: int = 1) -> dict:
    """Every orders column of clause 1.4.1 at its width; customer keys
    skip every third key, as the specification's do."""
    orderdate = r.integers(DATE_MIN, DATE_MAX - 151, size=rows,
                           dtype=np.int32)
    priority = r.integers(0, 5, size=rows, dtype=np.int8)
    totalprice = np.round(r.uniform(850.0, 560000.0, rows), 2)
    # Drawn after the columns above, so those stay as they were.
    k = r.integers(1, 100_000 * sf + 1, size=rows, dtype=np.int64)
    return {
        "o_orderkey": np.arange(1, rows + 1, dtype=np.int64),
        "o_custkey": k + (k - 1) // 2,
        "o_orderstatus": r.integers(0, len(ORDERSTATUS), size=rows,
                                    dtype=np.int8),
        "o_totalprice": totalprice,
        "o_orderdate": orderdate,
        "o_orderpriority": priority,
        "o_clerk": clerk_column(r, rows, 1_000 * sf),
        "o_shippriority": np.zeros(rows, np.int32),
        "o_comment": text_column(r, text_pool(r), rows, 19, 78, 79),
    }


GENERATORS = {"lineitem": gen_lineitem, "orders": gen_orders}


# ---------------------------------------------------------------------------
# Arithmetic in the reference's precision or the control's
# ---------------------------------------------------------------------------

class Float64:
    @staticmethod
    def load(x):
        return np.asarray(x, np.float64)

    @staticmethod
    def group_sum(x, groups, n):
        return np.bincount(groups, weights=x, minlength=n)


class BFloat16:
    """Every value and every partial result rounded to bfloat16."""

    @staticmethod
    def load(x):
        import ml_dtypes
        return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16)

    @classmethod
    def group_sum(cls, x, groups, n):
        x = cls.load(x) if x.dtype == np.float64 else x
        return np.asarray([float(cls._tree_sum(x[groups == g]))
                           for g in range(n)])

    @staticmethod
    def _tree_sum(x):
        if x.size == 0:
            return 0.0
        size = 1 << (x.size - 1).bit_length()
        t = np.concatenate([x, np.zeros(size - x.size, x.dtype)])
        while t.size > 1:
            t = t[: t.size // 2] + t[t.size // 2:]
        return t[0]


# ---------------------------------------------------------------------------
# Query answers: {column: array}, one row per group, groups in key order
# ---------------------------------------------------------------------------

def _grouped(keys: dict, values: dict, arith) -> dict:
    """Sum ``values`` by the combination of ``keys``, groups in key
    order: each key is coded by its distinct values, the codes are
    combined into one index, and the distinct indices are the groups."""
    n_rows = len(next(iter(values.values())))
    uniques, codes = [], []
    for k in keys.values():
        u, c = np.unique(np.asarray(k, np.int64), return_inverse=True)
        uniques.append(u)
        codes.append(c.ravel())
    dims = [len(u) for u in uniques]
    flat = np.ravel_multi_index(codes, dims) if keys \
        else np.zeros(n_rows, np.int64)
    groups, inv = np.unique(flat, return_inverse=True)
    at = np.unravel_index(groups, dims) if keys else ()
    out = {k: uniques[i][at[i]] for i, k in enumerate(keys)}
    for name, v in values.items():
        out[name] = np.asarray(arith.group_sum(v, inv.ravel(), len(groups)),
                               np.float64)
    return out


def q1(tables: dict, params: dict, arith=Float64) -> dict:
    li = tables["lineitem"]
    m = li["l_shipdate"] <= DATE_MAX - params["delta_days"]
    price = arith.load(li["l_extendedprice"][m])
    disc = arith.load(li["l_discount"][m])
    tax = arith.load(li["l_tax"][m])
    qty = arith.load(li["l_quantity"][m])
    one = arith.load(np.ones(1))[0]
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    return _grouped(
        {"l_returnflag": li["l_returnflag"][m],
         "l_linestatus": li["l_linestatus"][m]},
        {"sum_qty": qty, "sum_base_price": price,
         "sum_disc_price": disc_price, "sum_charge": charge,
         "sum_disc": disc, "count_order": arith.load(np.ones(m.sum()))},
        arith)


def q6(tables: dict, params: dict, arith=Float64) -> dict:
    li = tables["lineitem"]
    lo = first_of_year(params["year"])
    d = params["discount"]
    m = ((li["l_shipdate"] >= lo) & (li["l_shipdate"] < lo + 365)
         & (li["l_discount"] >= round(d - 0.01, 2))
         & (li["l_discount"] <= round(d + 0.01, 2))
         & (li["l_quantity"] < params["quantity"]))
    revenue = arith.load(li["l_extendedprice"][m]) \
        * arith.load(li["l_discount"][m])
    return _grouped({}, {"revenue": revenue}, arith)


def q12(tables: dict, params: dict, arith=Float64) -> dict:
    li, orders = tables["lineitem"], tables["orders"]
    lo = first_of_year(params["year"])
    m = (np.isin(li["l_shipmode"], [MAIL, SHIP])
         & (li["l_commitdate"] < li["l_receiptdate"])
         & (li["l_shipdate"] < li["l_commitdate"])
         & (li["l_receiptdate"] >= lo) & (li["l_receiptdate"] < lo + 365))
    okey = li["l_orderkey"][m]
    order = np.argsort(orders["o_orderkey"], kind="stable")
    sorted_keys = orders["o_orderkey"][order]
    at = np.minimum(np.searchsorted(sorted_keys, okey), len(sorted_keys) - 1)
    found = sorted_keys[at] == okey
    prio = orders["o_orderpriority"][order][at][found]
    high = np.isin(prio, [URGENT, HIGH]).astype(np.float64)
    return _grouped({"l_shipmode": li["l_shipmode"][m][found]},
                    {"high_line_count": arith.load(high),
                     "low_line_count": arith.load(1.0 - high)}, arith)


QUERIES = {"q1": q1, "q6": q6, "q12": q12}
