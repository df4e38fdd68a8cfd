"""Logical bytes of a query: rows x the sum, over the distinct columns
its group keys and its filter read, of the itemsize of that column as it
is resident on the device (asked of the system at run time). Every
statement reads every row of those columns."""
from __future__ import annotations

from typing import Callable, List


def columns_read(shape) -> List[str]:
    cols = set(shape["keys"])
    if shape["nonempty_phrase"]:
        cols.add("SearchPhrase")
    return sorted(cols)


def logical_bytes(shape, rows: int, itemsize: Callable[[str], int]) -> int:
    return rows * sum(itemsize(c) for c in columns_read(shape))
