"""Logical bytes of a query: rows x the sum, over the distinct columns
its group keys and aggregates read, of the itemsize of that column as it
is resident on the device (asked of the system at run time). Every
statement of the cell reads every row: no predicate."""
from __future__ import annotations

from typing import Callable, List

from .statements import KEYS


def columns_read(shape) -> List[str]:
    cols = [c for k in shape["keys"] for c in KEYS[k][1]]
    cols += [what for fn, what in shape["aggs"] if fn != "COUNT"]
    return sorted(set(cols))


def logical_bytes(shape, rows: int, itemsize: Callable[[str], int]) -> int:
    return rows * sum(itemsize(c) for c in columns_read(shape))
