"""The plain reference: the five statements evaluated with numpy over the
host columns the seed made, every sum taken exactly, in integers. It
imports nothing of the program and reads nothing the program produced.

An amount arrives as the correctly rounded double of a two-place decimal,
so ``rint(x * 100)`` is the integer of cents it was dealt as, exactly;
group sums split each addend into two halves whose float64 bincounts are
exact (every partial sum stays an integer under 2^53), and the segments
add in Python integers. An ``AVG`` is the exact total over 100 x the
exact count, one correctly rounded division. ``YEAR`` is the UTC year of
the pickup by ``datetime64``; ``ROUND`` is numpy's, half to even on the
double (a distance of 2.50 is in group 2.0, 3.50 in 4.0).

``TOLERANCE``, relative, is what ``same`` allows an ``AVG``: a float64
sum depends on its order, and a device that holds a double as a pair of
float32 keeps 48 bits of it (4e-15); a value or a partial sum held in
float32 anywhere on the path misses by 1e-11 and more on the large
groups. ``answer(..., round_to=numpy.float32)`` is that control (each
addend rounded to float32, then summed exactly): it has to read not
``same``. Groups and ``COUNT(*)`` are exact; rows come in any order the
statement's ``ORDER BY`` allows (any order without one).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark.ssb.data import Coded, Column

Rows = List[Tuple]
TOLERANCE = 1e-12
_HALF = 20                                # bits of a low half


def key_values(seg: Dict[str, Column], key: str
               ) -> Tuple[np.ndarray, np.ndarray]:
    """(the distinct values of group key ``key`` in a segment, each row's
    index among them)."""
    col = seg.get(key)
    if isinstance(col, Coded):
        u, inv = np.unique(col.codes, return_inverse=True)
        return np.asarray(col.values, dtype=object)[u], inv
    if key == "year":
        ms = seg["pickup_datetime"].astype("datetime64[ms]")
        col = ms.astype("datetime64[Y]").astype(np.int64) + 1970
    elif key == "distance":
        col = np.round(seg["trip_distance"])
    return np.unique(col, return_inverse=True)


def cents(seg: Dict[str, Column], col: str) -> np.ndarray:
    return np.rint(seg[col] * 100).astype(np.int64)


def _exact_group_sums(inverse: np.ndarray, vals: np.ndarray, n: int
                      ) -> List[int]:
    lo = np.bincount(inverse, weights=(vals & ((1 << _HALF) - 1)).astype(
        np.float64), minlength=n)
    hi = np.bincount(inverse, weights=(vals >> _HALF).astype(np.float64),
                     minlength=n)
    return [(int(h) << _HALF) + int(lo_) for h, lo_ in zip(hi, lo)]


def segment_state(seg: Dict[str, Column], shape, round_to=None) -> Dict:
    """{group key values: [count, total of each averaged column in
    cents]} of one segment. With ``round_to`` (the control) a total is a
    float: the sum of the values as that type holds them, in cents."""
    uniques, codes = zip(*(key_values(seg, k) for k in shape["keys"]))
    flat = np.zeros(len(codes[0]), dtype=np.int64)
    for u, c in zip(uniques, codes):
        flat = flat * len(u) + c
    groups, inverse = np.unique(flat, return_inverse=True)
    counts = np.bincount(inverse, minlength=len(groups))
    totals = []
    for fn, what in shape["aggs"]:
        if fn == "COUNT":
            continue
        if round_to is None:
            totals.append(_exact_group_sums(inverse, cents(seg, what),
                                            len(groups)))
        else:
            held = seg[what].astype(round_to).astype(np.float64)
            totals.append(list(np.bincount(inverse, weights=held,
                                           minlength=len(groups)) * 100))
    out = {}
    for g, idx in enumerate(groups):
        key, rem = [], int(idx)
        for u in reversed(uniques):
            key.append(u[rem % len(u)])
            rem //= len(u)
        key = tuple(x.item() if isinstance(x, np.generic) else x
                    for x in reversed(key))
        out[key] = [int(counts[g])] + [t[g] for t in totals]
    return out


def answer(segments: Sequence[Dict[str, Column]], shape,
           round_to=None) -> Rows:
    """The table's answer to ``shape``: per-segment exact states merged
    in Python integers, each average divided out once; rows sorted by the
    statement's ORDER BY (by the group keys where it has none, and
    within its ties), cut at its LIMIT. ``round_to`` is for the control
    only."""
    acc: Dict[Tuple, list] = {}
    for seg in segments:
        for k, state in segment_state(seg, shape, round_to).items():
            acc[k] = ([a + b for a, b in zip(acc[k], state)]
                      if k in acc else state)
    rows = []
    for key, state in acc.items():
        count, totals = state[0], iter(state[1:])
        row = list(key)
        for fn, _what in shape["aggs"]:
            row.append(count if fn == "COUNT"
                       else next(totals) / (100 * count))
        rows.append(tuple(row))
    rows.sort(key=lambda r: r[:len(shape["keys"])])
    if shape["order"]:
        rows.sort(key=lambda r: _order_key(shape, r))
    return rows[:shape["limit"]] if shape["limit"] is not None else rows


def _order_key(shape, row) -> Tuple:
    """The sort key of ``row`` under the statement's ORDER BY."""
    out = []
    for what, how in shape["order"]:
        x = row[len(shape["keys"]) + _count_at(shape)] if what == "count" \
            else row[shape["keys"].index(what)]
        out.append(-x if how == "desc" else x)
    return tuple(out)


def _count_at(shape) -> int:
    return [fn for fn, _w in shape["aggs"]].index("COUNT")


def worst_error(got, expected: Rows, shape) -> float:
    """The largest relative error of an ``AVG`` of ``got`` against
    ``expected``, group by group: the reading ``TOLERANCE`` is set
    against. Infinite where the groups or a ``COUNT(*)`` differ."""
    n_keys = len(shape["keys"])
    got = [tuple(r) for r in got] if got is not None else []
    by_key = {r[:n_keys]: r for r in got}
    if len(got) != len(expected) or len(by_key) != len(got):
        return math.inf
    worst = 0.0
    for e in expected:
        g = by_key.get(tuple(e[:n_keys]))
        if g is None or len(g) != len(e):
            return math.inf
        for (fn, _what), x, y in zip(shape["aggs"], g[n_keys:],
                                     e[n_keys:]):
            if x is None or (fn == "COUNT" and float(x) != y):
                return math.inf
            if fn != "COUNT":
                worst = max(worst, abs(float(x) - y) / abs(y) if y
                            else (0.0 if float(x) == 0 else math.inf))
    return worst


def same(got, expected: Rows, shape) -> bool:
    """Did the system answer ``shape`` with ``expected``: the same groups,
    ``COUNT(*)`` exact, every ``AVG`` within ``TOLERANCE`` relative, in an
    order the statement's ``ORDER BY`` allows (any, without one)."""
    if got is None:
        return False
    if shape["order"]:
        order = [_order_key(shape, r) for r in got]
        if any(a > b for a, b in zip(order, order[1:])):
            return False
    return worst_error(got, expected, shape) <= TOLERANCE
