"""Logical bytes of a query: rows x the sum, over the distinct columns
its predicates, value expression and group-by name, of the itemsize of
that column as it is resident on the device. The caller asks the system
for each column's resident itemsize at run time, so a later change that
narrows a column moves the bytes with it."""
from __future__ import annotations

from typing import Callable, List


def columns_read(shape) -> List[str]:
    cols = [c for c, _op, _v in shape["preds"]]
    cols += [c for c in shape["value"] if c not in ("*", "-", "+")]
    cols += list(shape["group"])
    return sorted(set(cols))


def logical_bytes(shape, rows: int, itemsize: Callable[[str], int]) -> int:
    return rows * sum(itemsize(c) for c in columns_read(shape))
