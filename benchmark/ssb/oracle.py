"""The plain reference: each shape evaluated with numpy over the host
columns the seed made. It imports nothing of the program and reads
nothing the program produced; sums are exact (integers well under 2^53
accumulated in float64, every partial sum an integer).

``answer`` returns rows as tuples of ``str`` and ``int`` in the
statement's ORDER BY; ``same`` compares what the system sent with them:
the same rows, and the same sequence of ORDER BY keys (rows whose keys
tie may come in either order).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from .data import Coded, Column

Rows = List[Tuple]


def _mask(col: Column, op: str, val) -> np.ndarray:
    if isinstance(col, Coded):
        # a predicate on strings is a look-up table over the values
        vals = col.values
        if op == "eq":
            lut = [v == val for v in vals]
        elif op == "in":
            lut = [v in set(val) for v in vals]
        elif op == "lt":
            lut = [v < val for v in vals]
        elif op == "between":
            lut = [val[0] <= v <= val[1] for v in vals]
        else:
            raise ValueError(f"unknown predicate op {op!r}")
        return np.asarray(lut, dtype=bool)[col.codes]
    if op == "eq":
        return col == val
    if op == "in":
        return np.isin(col, list(val))
    if op == "lt":
        return col < val
    if op == "between":
        return (col >= val[0]) & (col <= val[1])
    raise ValueError(f"unknown predicate op {op!r}")


def _values(seg: Dict[str, Column], expr: Sequence[str], mask) -> np.ndarray:
    def col(c):
        return seg[c][mask].astype(np.int64)

    if len(expr) == 1:
        return col(expr[0])
    a, op, b = expr
    return col(a) * col(b) if op == "*" else col(a) - col(b)


def _group_codes(col: Column, mask) -> Tuple[np.ndarray, list]:
    """Codes 0..card-1 of the masked rows and the value of each code."""
    if isinstance(col, Coded):
        return col.codes[mask].astype(np.int64), list(col.values)
    lo, hi = int(col.min()), int(col.max())
    return col[mask].astype(np.int64) - lo, list(range(lo, hi + 1))


def segment_sums(seg: Dict[str, Column], shape) -> Dict[Tuple, int]:
    """{group key: sum} of one segment; the key of an ungrouped shape is
    ``()``. Groups no row falls in are absent."""
    mask = None
    for c, op, val in shape["preds"]:
        m = _mask(seg[c], op, val)
        mask = m if mask is None else mask & m
    vals = _values(seg, shape["value"], mask)
    if not shape["group"]:
        return {(): int(vals.sum())}
    key = np.zeros(len(vals), dtype=np.int64)
    decode = []
    for c in shape["group"]:
        codes, names = _group_codes(seg[c], mask)
        key = key * len(names) + codes
        decode.append(names)
    space = math.prod(len(n) for n in decode)
    sums = np.bincount(key, weights=vals.astype(np.float64), minlength=space)
    cnts = np.bincount(key, minlength=space)
    out = {}
    for idx in np.nonzero(cnts)[0]:
        rem, parts = int(idx), []
        for names in reversed(decode):
            parts.append(names[rem % len(names)])
            rem //= len(names)
        out[tuple(reversed(parts))] = int(sums[idx])
    return out


def answer(segments: Sequence[Dict[str, Column]], shape,
           round_to=None) -> Rows:
    """The table's answer to ``shape``: per-segment sums merged, in the
    shape's ORDER BY. ``round_to`` is for the control only: a numpy float
    type that every sum is rounded to, as a path that accumulates in it
    would at best."""
    acc: Dict[Tuple, int] = {}
    for seg in segments:
        for k, v in segment_sums(seg, shape).items():
            acc[k] = acc.get(k, 0) + v
    if round_to is not None:
        acc = {k: int(round_to(v)) for k, v in acc.items()}
    rows = sorted(k + (v,) for k, v in acc.items())
    return sorted(rows, key=lambda r: order_key(shape, r))


def order_key(shape, row) -> Tuple:
    """The ORDER BY key of one answer row (group columns, then the sum).
    Only a number is ever ordered descending."""
    at = {c: i for i, c in enumerate(shape["group"])}
    at["SUM"] = len(shape["group"])
    return tuple(-row[at[c]] if way == "desc" else row[at[c]]
                 for c, way in shape.get("order", []))


def normal(rows) -> Rows:
    """Rows as tuples of ``str`` and ``int``: what the wire brings (JSON
    numbers, a SUM as a double) and what the reference computes compare
    equal exactly or not at all."""
    return [tuple(x if isinstance(x, str) else _as_int(x) for x in r)
            for r in rows]


def same(got, expected: Rows, shape) -> bool:
    """Did the system answer ``shape`` with ``expected``: the same rows,
    in an order that the statement's ORDER BY allows."""
    got = normal(got)
    if sorted(got, key=repr) != sorted(expected, key=repr):
        return False
    return ([order_key(shape, r) for r in got]
            == [order_key(shape, r) for r in expected])


def _as_int(x):
    if x is None:
        return None
    f = float(x)
    return int(f) if f == int(f) else f
