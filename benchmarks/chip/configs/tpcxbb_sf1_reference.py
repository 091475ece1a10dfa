"""Plain reference for ``tpcxbb_sf1``: the table generators and TPCx-BB
Q3's answer in numpy, independent of the program under test.

The generators make the columns of ``web_clickstreams`` that Q3 reads
and the ``item`` table's category, in the shapes of the program's
synthetic tables (uniform users, items, days and seconds of the day;
views, cart adds and purchases in the proportions 88, 9 and 3 of 100),
kept here so that a change to the program cannot move the data the
yardstick is computed on.

Q3's answer is computed over the whole table, as the specification
distributes it: every click sorted by user and ``tstamp =
wcs_click_date_sk * 86400 + wcs_click_time_sk``, ties broken by item and
click type; each purchase of an item in the target category walked back
over its user's clicks to the last ``views`` views, each counted where it
lies at most ``seconds`` before the purchase. The timestamps are exact
integers, or, for the control, ``bfloat16``: each timestamp and each
difference of two rounded to it. That is the 16-bit format one precision
below the program's 32-bit seconds that holds their range (an int16 or
a float16 cannot hold 31.5M), which the comparison has to catch.
"""
from __future__ import annotations

import numpy as np

VIEW, PURCHASE = 0, 2
CLICK_TYPES = (0.88, 0.09, 0.03)      # view, cart, purchase
SECONDS_A_DAY = 86400


def gen_clickstreams(rows: int, r: np.random.Generator, users: int,
                     items: int, days: int) -> dict:
    return {
        "wcs_user_sk": r.integers(0, users, rows, dtype=np.int64),
        "wcs_click_date_sk": r.integers(0, days, rows, dtype=np.int32),
        "wcs_click_time_sk": r.integers(0, SECONDS_A_DAY, rows,
                                        dtype=np.int32),
        "wcs_item_sk": r.integers(0, items, rows, dtype=np.int64),
        "wcs_click_type": r.choice(3, rows, p=CLICK_TYPES).astype(np.int8),
    }


def gen_item(rows: int, r: np.random.Generator, categories: int) -> dict:
    return {"i_item_sk": np.arange(rows, dtype=np.int64),
            "i_category_id": r.integers(0, categories, rows,
                                        dtype=np.int8)}


def exact(x):
    return x


def bfloat16(x):
    """``x`` rounded to bfloat16, held as float64."""
    import ml_dtypes
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


class Sessions:
    """Every click of the table in session order, sorted once for all the
    queries of a run; each timestamp and each difference of two passed
    through ``rounding`` (``exact``, or ``bfloat16`` for the control)."""

    def __init__(self, clicks: dict, rounding=exact):
        self.rounding = rounding
        user = np.asarray(clicks["wcs_user_sk"])
        tstamp = rounding(np.asarray(clicks["wcs_click_date_sk"], np.int64)
                          * SECONDS_A_DAY
                          + np.asarray(clicks["wcs_click_time_sk"]))
        item = np.asarray(clicks["wcs_item_sk"])
        ctype = np.asarray(clicks["wcs_click_type"])
        order = np.lexsort((ctype, item, tstamp, user))
        self.user = user[order]
        self.tstamp = tstamp[order]
        self.item = item[order]
        self.ctype = ctype[order]
        self.answers: dict = {}

    def bb_q3(self, categories: np.ndarray, params: dict) -> dict:
        """Views by viewed item: ``{"viewed_item", "views"}``, sorted by
        item."""
        key = (params["target_category"], params["views"], params["seconds"])
        if key not in self.answers:
            self.answers[key] = self._walk(categories, *key)
        return self.answers[key]

    def _walk(self, categories, target, views, seconds) -> dict:
        user, tstamp, item, ctype = self.user, self.tstamp, self.item, \
            self.ctype
        rounding = self.rounding
        counts: dict = {}
        purchases = np.flatnonzero((ctype == PURCHASE)
                                   & (categories[item] == target))
        for p in purchases.tolist():
            seen = 0
            j = p - 1
            while j >= 0 and seen < views and user[j] == user[p]:
                if ctype[j] == VIEW:
                    seen += 1
                    if rounding(tstamp[p] - tstamp[j]) <= seconds:
                        k = int(item[j])
                        counts[k] = counts.get(k, 0) + 1
                j -= 1
        keys = np.asarray(sorted(counts), np.int64)
        return {"viewed_item": keys,
                "views": np.asarray([counts[k] for k in keys.tolist()],
                                    np.float64)}


GENERATORS = {"clickstreams": gen_clickstreams, "item": gen_item}
