"""Logical bytes of a query: rows x the sum, over the distinct columns
its predicates, aggregate expressions and group-by name, of the itemsize
of that column as it is resident on the device (asked of the system at
run time: a DOUBLE column counts its 8 bytes however the device splits
them)."""
from __future__ import annotations

from typing import Callable, List

from .statements import EXPRESSIONS


def columns_read(shape) -> List[str]:
    cols = [c for c, _op, _v in shape["preds"]]
    for fn, what in shape["aggs"]:
        if fn != "COUNT":
            cols += EXPRESSIONS[what][1]
    cols += list(shape["group"])
    return sorted(set(cols))


def logical_bytes(shape, rows: int, itemsize: Callable[[str], int]) -> int:
    return rows * sum(itemsize(c) for c in columns_read(shape))
