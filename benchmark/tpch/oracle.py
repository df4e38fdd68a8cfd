"""The plain reference: Q1 and Q6 evaluated with numpy over the host
columns the seed made, every sum taken exactly, in integers. It imports
nothing of the program and reads nothing the program produced.

A measure arrives as the correctly rounded double of a decimal with at
most two places, so ``rint(x * 100)`` is the integer it was dealt as
(cents, hundredths), exactly. A row's ``l_extendedprice * (1 -
l_discount) * (1 + l_tax)`` is then cents x (100 - hundredths) x (100 +
hundredths): under 1.2e11 a row, under 1e18 a segment of 2^23 rows, so
int64 holds a segment's sum and Python integers add the segments. Group
sums split each int64 addend into two halves whose float64 bincounts are
exact (every partial sum stays an integer under 2^53). A ``SUM`` is the
exact total over its scale, one correctly rounded division; an ``AVG`` is
the exact total over scale x exact count.

``TOLERANCE``, relative, is what ``same`` allows a ``SUM`` or an ``AVG``:

- why not 0: a float64 sum depends on its order. Pinot's own doubles
  differ from run to run in the last digits by the order its segments
  combine; a blocked float64 summation of 3e7 addends stays under 1e-13
  of the exact value, and a device that holds a double as a pair of
  float32 (XLA:TPU) keeps 48 bits of it, 4e-15;
- why not more: a value or a partial sum held in float32 anywhere on the
  path misses by about 5e-12 on the three large Q1 groups and 5e-11 on
  (N, F) (6e-8 a value, over the root of 2e7 or 4e5 rows), and a float32
  accumulator by 1e-7 and more. ``answer(..., round_to=numpy.float32)``
  is that control (each addend rounded to float32, then summed exactly):
  it has to read not ``same`` on every seed
  (``benchmark/tests/control_tpch_full_size.py``, PERF.md section 4).

Groups, their order and ``COUNT(*)`` are exact.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark.ssb.data import Coded, Column

Rows = List[Tuple]
TOLERANCE = 1e-12

HUNDREDTHS = ("l_extendedprice", "l_discount", "l_tax")   # two places
_HALF = 20                                                # bits of a low half


def integers(seg: Dict[str, Column], col: str) -> np.ndarray:
    """The integers a measure column was dealt as: units of l_quantity,
    cents or hundredths of the others."""
    x = seg[col]
    return np.rint(x * 100 if col in HUNDREDTHS else x).astype(np.int64)


def _mask(seg: Dict[str, Column], preds) -> np.ndarray:
    mask = None
    for col, op, val in preds:
        if seg[col].dtype.kind == "f":            # compare as integers
            x = integers(seg, col)
            scale = 100 if col in HUNDREDTHS else 1
            val = ([round(v * scale) for v in val] if op == "between"
                   else round(val * scale))
        else:
            x = seg[col]
        if op == "le":
            m = x <= val
        elif op == "lt":
            m = x < val
        elif op == "ge":
            m = x >= val
        elif op == "between":
            m = (x >= val[0]) & (x <= val[1])
        else:
            raise ValueError(f"unknown predicate op {op!r}")
        mask = m if mask is None else mask & m
    return mask


# what divides an expression's integer addend: 1, 100 (cents, hundredths)
# and their products
SCALES = {"l_quantity": 1, "l_extendedprice": 100, "l_discount": 100,
          "disc_price": 10_000, "revenue": 10_000, "charge": 1_000_000}


def addends(seg: Dict[str, Column], what: str, mask) -> np.ndarray:
    """The int64 addend of every matching row, ``SCALES[what]`` times the
    expression's value."""
    def ints(col):
        return integers(seg, col)[mask]

    if what in ("l_quantity", "l_extendedprice", "l_discount"):
        return ints(what)
    price = ints("l_extendedprice")
    if what == "revenue":
        return price * ints("l_discount")
    disc_price = price * (100 - ints("l_discount"))
    if what == "disc_price":
        return disc_price
    if what == "charge":
        return disc_price * (100 + ints("l_tax"))
    raise ValueError(f"unknown expression {what!r}")


def _exact_group_sums(key: np.ndarray, vals: np.ndarray, space: int
                      ) -> List[int]:
    lo = np.bincount(key, weights=(vals & ((1 << _HALF) - 1)).astype(
        np.float64), minlength=space)
    hi = np.bincount(key, weights=(vals >> _HALF).astype(np.float64),
                     minlength=space)
    return [(int(h) << _HALF) + int(l) for h, l in zip(hi, lo)]


def segment_state(seg: Dict[str, Column], shape, round_to=None) -> Dict:
    """{group codes: [count, total of each distinct expression]} of one
    segment; the key of an ungrouped shape is ``()``. With ``round_to``
    (the control) a total is a float: the sum of the addends as that type
    holds them."""
    mask = _mask(seg, shape["preds"])
    cards = [len(seg[c].values) for c in shape["group"]]
    key = np.zeros(int(mask.sum()), dtype=np.int64)
    for c, card in zip(shape["group"], cards):
        key = key * card + seg[c].codes[mask]
    space = int(np.prod(cards)) if cards else 1
    counts = np.bincount(key, minlength=space)
    totals = []
    for what in _expressions(shape):
        vals = addends(seg, what, mask)
        if round_to is None:
            totals.append(_exact_group_sums(key, vals, space))
        else:
            held = (vals / SCALES[what]).astype(round_to).astype(np.float64)
            totals.append(list(np.bincount(key, weights=held,
                                           minlength=space) * SCALES[what]))
    out = {}
    for idx in np.nonzero(counts)[0]:
        codes, rem = [], int(idx)
        for card in reversed(cards):
            codes.append(rem % card)
            rem //= card
        out[tuple(reversed(codes))] = [int(counts[idx])] + [
            t[idx] for t in totals]
    return out


def _expressions(shape) -> List[str]:
    return list(dict.fromkeys(w for fn, w in shape["aggs"] if fn != "COUNT"))


def answer(segments: Sequence[Dict[str, Column]], shape,
           round_to=None) -> Rows:
    """The table's answer to ``shape``: per-segment exact states merged
    in Python integers, each aggregate divided out once, rows in the
    shape's ORDER BY (its group columns, ascending). An ungrouped shape
    no row matches answers nothing here: the statements of the cell
    always match rows. ``round_to`` is for the control only."""
    acc: Dict[Tuple, list] = {}
    for seg in segments:
        for k, state in segment_state(seg, shape, round_to).items():
            acc[k] = ([a + b for a, b in zip(acc[k], state)]
                      if k in acc else state)
    exprs = _expressions(shape)
    names = [segments[0][c].values for c in shape["group"]]
    rows = []
    for codes, state in acc.items():
        count, totals = state[0], dict(zip(exprs, state[1:]))
        row = [vals[c] for vals, c in zip(names, codes)]
        for fn, what in shape["aggs"]:
            if fn == "COUNT":
                row.append(count)
            elif fn == "SUM":
                row.append(totals[what] / SCALES[what])
            else:
                row.append(totals[what] / (SCALES[what] * count))
        rows.append(tuple(row))
    return sorted(rows, key=lambda r: r[:len(shape["group"])])


def same(got, expected: Rows, shape) -> bool:
    """Did the system answer ``shape`` with ``expected``: the same groups
    in the same order, ``COUNT(*)`` exact, every ``SUM`` and ``AVG``
    within ``TOLERANCE`` relative."""
    if got is None or len(got) != len(expected):
        return False
    n_group = len(shape["group"])
    for g, e in zip(got, expected):
        if len(g) != len(e) or list(g[:n_group]) != list(e[:n_group]):
            return False
        for (fn, _what), x, y in zip(shape["aggs"], g[n_group:],
                                     e[n_group:]):
            if x is None:
                return False
            if fn == "COUNT":
                if float(x) != y:
                    return False
            elif abs(float(x) - y) > TOLERANCE * abs(y):
                return False
    return True
