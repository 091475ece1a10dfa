"""TPCx-BB Q3's session window as one traced program.

Backs the ``clicks_before_purchase`` UDF on the jit backend. A fragment
holds whole user sessions (the optimizer's exchange on ``wcs_user_sk``);
``session_window`` takes its columns at the int32 jit boundary and, in
one ``jax.jit`` program:

* sorts the rows with one ``jax.lax.sort`` by the order the plan declares
  (``(wcs_user_sk, tstamp, wcs_item_sk, wcs_click_type)`` for BB-Q3),
  which has to name all four columns;
* finds the view before every row with a running maximum of view
  positions, and the qualifying purchases (click type 2, the item's
  category, a gather from the broadcast category table, equal to the
  target) by a binary search over their running count, so that only
  they are walked;
* walks each purchase back over its last ``views`` views, one gather a
  step, keeps a view where it belongs to the purchase's user and lies at
  most ``seconds`` before it, and counts the kept views by viewed item
  in one scatter-add.

It returns a count per item of the category table, and the number of
qualifying purchases. The caller pads rows to a power of two so that
fragment sizes map onto a few shapes, with the largest value of the
first sort key in the padding rows so that they sort last, and gives
``max_purchases`` room for every purchase of the fragment. The program
is plain ``jax.numpy``, like ``kernels/hash_join.py``: its gathers reach
anywhere in the fragment, and the whole fragment does not fit the
chip's scoped VMEM. The host side, which checks that a batch fits the
int32 program, pads it and turns the counts into the UDF's rows, is the
jit backend's (``engine/compile.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

VIEW, PURCHASE = 0, 2
USER, TSTAMP, ITEM, CTYPE = ("wcs_user_sk", "tstamp", "wcs_item_sk",
                             "wcs_click_type")
COLUMNS = (USER, TSTAMP, ITEM, CTYPE)


@functools.partial(jax.jit,
                   static_argnames=("names", "views", "max_purchases"))
def session_window(cols, n_valid, cats, target, seconds, *, names, views,
                   max_purchases):
    """Counts by viewed item of the windowed views before each qualifying
    purchase. ``cols`` holds the columns named by ``names``, in their
    sort order; ``cats`` is the category of each item. The first
    ``max_purchases`` qualifying purchases are counted, so the caller
    makes it at least their number. Returns
    ``(counts[len(cats)], purchases)``, ``purchases`` counting them all."""
    n = cols[0].shape[0]
    # Every column is a key, so rows that tie are equal in every column
    # and an unstable sort gives the stable sort's result.
    c = dict(zip(names, jax.lax.sort(tuple(cols), num_keys=len(cols))))
    user, tstamp, item, ctype = (c[k] for k in COLUMNS)
    rows = jnp.arange(n, dtype=jnp.int32)
    valid = rows < n_valid
    # The view before each row: a running maximum of view positions.
    views_at = jnp.where((ctype == VIEW) & valid, rows, -1)
    prev = jnp.concatenate([jnp.full(1, -1, jnp.int32),
                            jax.lax.cummax(views_at)[:-1]])
    # The qualifying purchases' positions, in order: the k-th is where
    # the running count of them first reaches k.
    purchase = (ctype == PURCHASE) & valid & (cats[item] == target)
    running = jnp.cumsum(purchase.astype(jnp.int32))
    at = jnp.minimum(jnp.searchsorted(
        running, jnp.arange(1, max_purchases + 1, dtype=jnp.int32)),
        n - 1).astype(jnp.int32)
    live = jnp.arange(max_purchases) < running[-1]
    n_items = cats.shape[0]
    slots = []
    pos = at
    for _ in range(views):
        pos = prev[jnp.maximum(pos, 0)]
        v = jnp.maximum(pos, 0)
        keep = live & (pos >= 0) & (user[v] == user[at]) \
            & (tstamp[at] - tstamp[v] <= seconds)
        # A view left out lands past the table and is dropped.
        slots.append(jnp.where(keep, item[v], n_items))
    counts = jnp.zeros(n_items, jnp.int32).at[
        jnp.concatenate(slots)].add(1, mode="drop")
    return counts, running[-1]
